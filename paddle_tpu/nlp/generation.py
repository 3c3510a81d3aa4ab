"""Autoregressive generation with a static-shape KV cache.

Upstream analogue: PaddleNLP `paddlenlp/transformers/generation_utils.py`
(GenerationMixin.generate: greedy / sampling / top-k / top-p with
incremental decode). TPU-native design: instead of growing KV tensors
(which would recompile every step), the cache is allocated once at
`prompt_len + max_new_tokens` and updated in place with
`lax.dynamic_update_slice`; the whole decode is ONE XLA program — a
prefill call followed by a `lax.while_loop` over single-token steps with
early exit when every sequence has emitted EOS.
"""
from __future__ import annotations

import threading
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import framework
from .. import observability as _obs
from ..jit import functional_call, functional_method, functional_state
from ..tensor import Tensor, to_jax

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _record_spec_stats(rounds: int, emitted: int, accepted: int,
                       proposed: int, source: str = 'generate'):
    """Mirror speculative-decode stats into the shared registry
    (`paddle_spec_*`, labeled by source) so standalone
    `speculative_generate()` and the serving engine's per-slot
    speculation report acceptance through ONE surface instead of
    ad-hoc per-call stats dicts."""
    if not _obs.enabled():
        return
    reg = _obs.get_registry()
    reg.counter('paddle_spec_rounds_total',
                'speculative-decode rounds by source',
                ('source',)).labels(source=source).inc(rounds)
    reg.counter('paddle_spec_emitted_tokens_total',
                'tokens emitted by speculative decode by source',
                ('source',)).labels(source=source).inc(emitted)
    reg.counter('paddle_spec_proposed_drafts_total',
                'draft tokens proposed by source',
                ('source',)).labels(source=source).inc(proposed)
    reg.counter('paddle_spec_accepted_drafts_total',
                'draft tokens accepted by source',
                ('source',)).labels(source=source).inc(accepted)

# warn-once latch for the prompt-already-at-max_length case (tests reset it)
_warned_max_length = [False]


def as_offset(position_offset):
    """Normalize a position offset (None / int / [B] array / Tensor) to a
    traced i32 (scalar, or [B] for per-sequence offsets — left-padded
    prompts give each sequence its own logical position origin)."""
    if position_offset is None:
        return jnp.int32(0)
    if isinstance(position_offset, Tensor):
        return position_offset.value.astype(jnp.int32)
    return jnp.asarray(position_offset, jnp.int32)


def offset_grid(offset, s):
    """Logical positions of `s` consecutive tokens starting at `offset`:
    scalar offset -> [S]; per-sequence [B] offset -> [B, S]."""
    ar = jnp.arange(s, dtype=jnp.int32)
    if jnp.ndim(offset) >= 1:
        return offset[:, None] + ar[None, :]
    return offset + ar


def update_kv_cache(k_cache, v_cache, k, v, offset):
    """Write new K/V blocks into the static decode cache at `offset`.
    All args are Tensors; [B, L, H_kv, D] caches, [B, S, H_kv, D] updates
    — or rows of any trailing rank: a latent entry's leaves are [B, L, C]
    (`latent_layers`), written the same way.
    `offset` is a scalar slot shared by the whole batch, or a [B] array of
    per-row slots (the serving engine's slot pool, where every sequence
    decodes at its own position). Returns (k_cache, v_cache) Tensors.
    Shared by every causal-LM family so decode-cache semantics can never
    diverge between models.

    Per-row offsets store all B x S rows by ONE scatter whose windows
    are whole [H_kv, D] rows at explicit (slot, row) indices. The shape
    matters on the chip: the v5e compiler runs such a scatter natively
    on the donated leaf, where `vmap(dynamic_update_slice)` — a scatter
    that keeps the rows as a window dimension — was expanded into a
    `while` over the slots, twice a layer, in every decode sub-step
    (0.8 of serve-chat's 14.6 ms: PERF.md, PR 27). A block that would
    run past the slot's end starts at `L - S`, as `dynamic_update_slice`
    clamps it."""
    from ..tensor import apply_op as _apply
    off = offset.value if isinstance(offset, Tensor) else offset

    def upd(c, new):
        new = new.astype(c.dtype)
        if jnp.ndim(off) >= 1:
            b, s = new.shape[:2]
            start = jnp.clip(jnp.asarray(off, jnp.int32), 0, c.shape[1] - s)
            return c.at[jnp.arange(b, dtype=jnp.int32)[:, None],
                        offset_grid(start, s)].set(
                new, indices_are_sorted=True, unique_indices=True,
                mode='promise_in_bounds')
        return jax.lax.dynamic_update_slice(
            c, new, (0, off) + (0,) * (c.ndim - 2))
    return (_apply(upd, k_cache, k, _name='cache_update'),
            _apply(upd, v_cache, v, _name='cache_update'))


def decode_mask(q, k_cache, offset):
    """[1, 1, Sq, L] boolean causal mask for attention over a static cache:
    query at cache slot offset+i sees key slots <= offset+i. (`offset`
    here is the SLOT offset; for unpadded prompts slot == logical
    position.)"""
    from ..tensor import apply_op as _apply

    def fn(qv, kc):
        s, l = qv.shape[1], kc.shape[1]
        q_pos = offset + jnp.arange(s, dtype=jnp.int32)
        k_pos = jnp.arange(l, dtype=jnp.int32)
        return (k_pos[None, :] <= q_pos[:, None])[None, None]
    return _apply(fn, q, k_cache, _name='decode_mask')


def attended_rows(k_cache, v_cache, mask):
    """The rows of a static cache that attention contracts over: the
    first `mask.shape[-1]` of each leaf. A caller that knows no query
    looks past row `n` hands in a mask of `n` columns (the serving
    engine's shorter decode program, picked from the batch's positions)
    and attention then reads `[B, n, H_kv, D]` of each leaf, not the
    whole of it. The WRITE is not this function's: `update_kv_cache`
    scatters into the whole leaf, and the whole leaf is what a forward
    returns. A mask as long as the cache gives the leaves back as they
    came, so that caller's program is the one it was."""
    from ..tensor import apply_op as _apply
    rows = mask.shape[-1]
    if rows == k_cache.shape[1]:
        return k_cache, v_cache
    return tuple(_apply(lambda c: c[:, :rows], c, _name='attended_rows')
                 for c in (k_cache, v_cache))


def bounded_decode_attention(q, k_cache, v_cache, mask, sink=None):
    """Decode attention bounded per slot: where `ops.pallas.
    kv_decode_kernel` takes the call (its conditions are the whole
    choice: one float32 query a slot, a boolean mask shared by the
    heads, no sink, a TPU), q `[B, 1, H, D]` against the leaves WHOLE —
    the kernel bounds its own reading by the mask, whose columns may be
    fewer than the leaves' rows, and a slice of a leaf is a copy to it
    — through ONE kernel that walks each slot's row tiles from the
    first row the mask shows to the last and reads each once for the
    scores and the values; a slot that is not decoding (`active_rows`)
    is shown nothing — its output is discarded — and walks one tile.
    Logits over `sqrt(D)`, as `_attention_xla`'s. -> `[B, 1, H, Dv]`, or
    None: the call is `attended_rows`' and `_attention_xla`'s."""
    from ..ops import pallas as _pallas
    from ..tensor import apply_op as _apply
    kernel = _pallas.kv_decode_kernel(q, k_cache, v_cache, mask, sink)
    if kernel is None:
        return None

    def f(qv, kc, vc, m, *active):
        seen = jnp.broadcast_to(m[:, 0, 0], (qv.shape[0], m.shape[-1]))
        if active:
            seen = seen & active[0][:, None]
        out = kernel(qv[:, 0], kc, vc, seen, qv.shape[-1] ** -0.5)
        return out[:, None].astype(qv.dtype)
    active = active_rows()
    return _apply(f, q, k_cache, v_cache, mask,
                  *(() if active is None else (Tensor(active),)),
                  _name='kv_decode_attention')


def bounded_decode_tile(heads, entry, slots, rows, sink=None):
    """The row tile `bounded_decode_attention` walks by in a decode
    sub-step of `slots` float32 queries of `heads` heads over the cache
    `entry` (K, V: arrays or their specs) under a mask of `rows`
    columns, None where it gives the call back: the same dispatch,
    asked with that call's shapes. What a model's `decode_tiles` tells
    the serving engine, layer by layer."""
    from ..ops import pallas as _pallas
    k, v = entry
    spec = jax.ShapeDtypeStruct
    kernel = _pallas.kv_decode_kernel(
        spec((slots, 1, heads, k.shape[-1]), jnp.float32), k, v,
        spec((slots, 1, 1, rows), jnp.bool_), sink)
    return kernel and kernel.keywords['tile']


def padded_decode_mask(keep, cache_len, cache_offset, sq):
    """[B, 1, Sq, L] boolean mask for decode over a static cache holding a
    left/right-PADDED prompt: slot-causal AND key slot not a pad slot.
    `keep`: [B, S_prompt] bool (1 = real token); generated slots are
    always kept. Self-attention is always allowed so a fully-padded row
    can never produce an all-masked softmax (NaN)."""
    b, s_prompt = keep.shape
    k_slot = jnp.arange(cache_len, dtype=jnp.int32)
    q_slot = cache_offset + jnp.arange(sq, dtype=jnp.int32)
    causal = k_slot[None, :] <= q_slot[:, None]              # [Sq, L]
    keep_full = jnp.concatenate(
        [keep.astype(bool),
         jnp.ones((b, cache_len - s_prompt), bool)], axis=1)  # [B, L]
    self_ok = k_slot[None, :] == q_slot[:, None]             # [Sq, L]
    m = causal[None] & (keep_full[:, None, :] | self_ok[None])
    return m[:, None]                                        # [B,1,Sq,L]


class _RoutingState(threading.local):
    def __init__(self):
        self.picks = None
        self.active = None      # `routing_scope(active)`
        self.folded = None      # `state_scope`


_routing = _RoutingState()


class routing_scope:
    """`with routing_scope() as picks: fwd(...)` — what the expert layers
    traced inside the block routed to, in the idiom of the serving
    engine's `adapter_scope`: trace-time thread-local state, inert
    outside a scope. `picks` gets one `(selected [B, S, k] int32 raw
    array, number of experts, whether the layer's routed experts are
    the kernel)` per expert layer, in layer order; a model without an
    expert layer leaves it empty, and the program traced around it is
    the one it was. `active` (`[B]` boolean, or None) is which rows of
    the batch are decoding — a serving slot that is free or prefilling
    rides every sub-step too, and what it computes is discarded: a
    layer that can spend less on such a row asks `active_rows()`; one
    that does not ask traces what it did."""

    __slots__ = ('_prev', '_picks', '_active')

    def __init__(self, active=None):
        self._active = active

    def __enter__(self):
        self._prev = _routing.picks, _routing.active
        self._picks = _routing.picks = []
        _routing.active = self._active
        return self._picks

    def __exit__(self, *exc):
        _routing.picks, _routing.active = self._prev
        return False


def active_rows():
    """`[B]` boolean, the rows the enclosing `routing_scope` says are
    decoding; None outside a scope or where it was given none (every
    row's output is wanted)."""
    return _routing.active


def note_routing(selected, num_experts, kernel=False, share=False):
    """Called by an expert layer with the experts it selected, how many
    it HOLDS, and whether the call's routed experts run as the pallas
    kernel (`ops.pallas.expert_kernel`) and not as the loop over
    blocks. A layer that holds a share of its router's experts
    (`share`) gives `selected` in its own numbering, `num_experts` for
    a pick it does not hold."""
    if _routing.picks is not None:
        _routing.picks.append((to_jax(selected), int(num_experts),
                               bool(kernel), bool(share)))


def experts_touched(picks, active):
    """[2, number of expert layers] int32: per layer, how many distinct
    experts of those it holds the rows of `active` ([B] bool) routed
    to, and under it 1 where the layer ran the kernel (a constant of
    the program: it survives a program that is loaded and never traced
    here); None without an expert layer. Where a layer holds a share
    of its experts, two rows more: the picks of the active rows, and
    those of them that landed on an expert held here."""
    if not picks:
        return None
    share = any(p[3] for p in picks)
    touched, made, held = [], [], []
    for sel, e, _, _ in picks:
        hit = (sel[..., None] == jnp.arange(e, dtype=sel.dtype)) \
            & active[:, None, None, None]
        touched.append(jnp.sum(jnp.any(hit, axis=(0, 1, 2)),
                               dtype=jnp.int32))
        if share:
            made.append(jnp.sum(active, dtype=jnp.int32)
                        * (sel.size // sel.shape[0]))
            held.append(jnp.sum(hit, dtype=jnp.int32))
    rows = [jnp.stack(touched),
            jnp.asarray([k for _, _, k, _ in picks], jnp.int32)]
    if share:
        rows += [jnp.stack(made), jnp.stack(held)]
    return jnp.stack(rows)


def state_layers(cache):
    """The indices of a cache's entries that are not a pair of row
    leaves but recurrent slot STATE: one leaf `[B, ...]` with no row
    axis (a short convolution's last inputs, `nlp/lfm2.py`), or several
    such leaves in a pytree that is no tuple or list (`nlp/ling3.py`'s
    `{'S': the matrix state, 'conv': the convolutions' inputs}`). Empty
    for a model that keeps K and V only."""
    return tuple(i for i, entry in enumerate(cache)
                 if not isinstance(entry, (tuple, list)))


def ring_layers(cache, max_length):
    """The indices of a cache's (K, V) entries that hold fewer rows
    than `max_length`: RINGS, in which position p lives in row `p mod
    rows` (a window layer that keeps its window and no more). A ring
    shares with a state (`state_layers`) what the serving engine has to
    know: its rows cannot be hidden by a mask of positions or rewound —
    a row past the live position has REPLACED one the window still
    needs — so whoever seats it gives it as it stands at the prompt's
    real end, and nothing may share or rewind it. Empty for a model
    whose every layer keeps `max_length` rows."""
    return tuple(i for i, entry in enumerate(cache)
                 if isinstance(entry, (tuple, list))
                 and entry[0].shape[1] < max_length)


def latent_layers(cache):
    """The indices of a cache's entries that are LATENT: a pair of
    leaves `[B, L, C]` with rows and no head axis (latent attention: a
    token's compressed K/V and its shared rotary key, read by every
    head). Its rows are what K and V rows are — position p in row p,
    hidden above a position by a mask, shareable up to one, rewindable
    — so whatever serves K and V rows serves these, at their own row
    bytes. Empty for a model that keeps K and V by head."""
    return tuple(i for i, entry in enumerate(cache)
                 if isinstance(entry, (tuple, list))
                 and len(entry[0].shape) == 3)


def _ring_newest(last, rows):
    """[..., rows] int32: the newest position `p <= last` with `p mod
    rows == r`, for every row r (negative where the ring has not yet
    been filled that far)."""
    r = jnp.arange(rows, dtype=jnp.int32)
    last = jnp.asarray(last, jnp.int32)[..., None]
    return last - jnp.mod(last - r, rows)


def update_ring_cache(k_cache, v_cache, k, v, offset):
    """`update_kv_cache` for a ring: the call's tokens stand at
    positions `offset + i` and position p is written to row `p mod
    rows`. Of the call's S tokens the first `folded_tokens(S)` enter
    (`state_scope`: a right-padded prompt's padding must not, for in a
    ring it would replace rows the window still needs); every row ends
    up holding the newest position folded so far that maps to it.

    A single token outside a scope — a decode sub-step — is one scatter
    of a row a slot, as `update_kv_cache`'s; a longer call gathers, for
    every ring row, the call's newest token that maps to it (or keeps
    the row), since two of its tokens may map to one row."""
    from ..tensor import apply_op as _apply
    off = offset.value if isinstance(offset, Tensor) else offset
    s = k.shape[1]
    folded = folded_tokens(s)

    def upd(c, new):
        new = new.astype(c.dtype)
        b, rows = new.shape[0], c.shape[1]
        at = jnp.broadcast_to(jnp.asarray(off, jnp.int32), (b,))
        if s == 1 and folded == 1:
            return c.at[jnp.arange(b, dtype=jnp.int32)[:, None],
                        jnp.mod(at, rows)[:, None]].set(
                new, indices_are_sorted=True, unique_indices=True,
                mode='promise_in_bounds')
        newest = _ring_newest(at + folded - 1, rows)          # [B, rows]
        mine = newest >= at[:, None]
        take = jnp.clip(newest - at[:, None], 0, s - 1)
        fresh = jnp.take_along_axis(new, take[:, :, None, None], axis=1)
        return jnp.where(mine[:, :, None, None], fresh, c)
    return (_apply(upd, k_cache, k, _name='ring_update'),
            _apply(upd, v_cache, v, _name='ring_update'))


def ring_mask(offset, batch, sq, rows, window):
    """[B, 1, Sq, rows (+ Sq)] boolean: what the queries at positions
    `offset + i` see of a ring of `rows` rows.

    One query (a decode sub-step), AFTER its own write: row r holds the
    newest position `p <= offset` with `p mod rows == r`, visible iff
    `p >= 0` — `rows <= window`, so whatever the ring holds is inside
    the window. Several queries, BEFORE their write (their rows would
    replace what the first of them still needs): the ring as the call
    found it, each row visible iff it holds a position and that lies
    inside the query's window, followed by the call's own Sq tokens,
    causal and windowed among themselves."""
    at = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (batch,))
    if sq == 1:
        return (_ring_newest(at, rows) >= 0)[:, None, None, :]
    held = _ring_newest(at - 1, rows)                         # [B, rows]
    q_pos = at[:, None] + jnp.arange(sq, dtype=jnp.int32)     # [B, Sq]
    old = (held[:, None, :] >= 0) \
        & (q_pos[:, :, None] - held[:, None, :] < window)
    i = jnp.arange(sq, dtype=jnp.int32)
    own = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
    own = jnp.broadcast_to(own[None], (batch, sq, sq))
    return jnp.concatenate([old, own], axis=-1)[:, None]


class state_scope:
    """`with state_scope(n): fwd(ids, ...)` — a layer with recurrent
    state folds only the first `n` (a traced scalar) of the call's
    tokens into the state it returns; its outputs are the whole call's.
    The serving engine's prefill of a right-padded prompt of `s` tokens
    runs under `state_scope(s - 1)`: the padding never enters the state,
    and neither does the last prompt token, which the first decode
    sub-step forwards again (for K and V an identical overwrite; a
    recurrent state would take it twice). Trace-time thread-local state
    in the idiom of `routing_scope`; outside a scope a call folds all
    of its tokens."""

    __slots__ = ('_n', '_prev')

    def __init__(self, n):
        self._n = n

    def __enter__(self):
        self._prev = _routing.folded
        _routing.folded = self._n
        return self

    def __exit__(self, *exc):
        _routing.folded = self._prev
        return False


def folded_tokens(s):
    """How many of a call's `s` tokens enter the recurrent state."""
    return s if _routing.folded is None else _routing.folded


def _process_logits(logits, temperature, top_k, top_p):
    """Filter a [B, V] logits slab for sampling. Static config → traced fine."""
    logits = logits.astype(jnp.float32)
    if temperature != 1.0:
        logits = logits / jnp.maximum(temperature, 1e-6)
    v = logits.shape[-1]
    if top_k and 0 < top_k < v:
        # lax.top_k touches k values instead of sorting the whole vocab
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p and top_p < 1.0:
        # full descending sort via top_k(v) — one primitive for both paths
        srt = jax.lax.top_k(logits, v)[0]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until cumulative prob exceeds top_p (always keep top-1)
        cutoff_idx = jnp.sum((cum - probs) < top_p, axis=-1) - 1
        cutoff = jnp.take_along_axis(srt, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, _NEG_INF, logits)
    return logits


def _next_token(logits, key, strategy, temperature, top_k, top_p):
    """Sample the next token; returns (token, its log-prob under the raw
    model distribution)."""
    if strategy == 'greedy_search':
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        filtered = _process_logits(logits, temperature, top_k, top_p)
        tok = jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok_logp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok, tok_logp


def cached_forward(model, params, frozen, buffers):
    """The one cached-decode forward contract: returns
    ``fwd(tok, cache, pos_offset, slot, mask) -> (logits, new_cache)``
    running `model` functionally with the given state bound in. Shared by
    the greedy/sampling and beam decode loops below AND by the serving
    engine's slot-pooled decode step (paddle_tpu.serving.engine), so the
    decode-step semantics (position origin, cache slot, mask override)
    can never diverge between the batch and continuous-batching paths.
    `pos_offset`/`slot` may be scalars or per-row [B] arrays.
    A `slot` that is the LITERAL 0 (a Python integer, a constant of the
    caller's program, where a traced value may be anything) with `mask`
    None says the call brings every row its queries may see: a whole
    prefill into an empty slot. A model whose attention over its own
    tokens differs from its attention over rows held (latent attention)
    chooses by that; every other model reads 0 as it reads an array."""
    def fwd(tok, cache, pos_offset, slot, mask):
        (logits, new_cache), _ = functional_call(
            model, params, frozen, buffers, (tok,),
            dict(cache=cache, position_offset=pos_offset,
                 cache_offset=slot, attention_mask=mask,
                 use_cache=True))
        return logits, new_cache
    return fwd


class GenerationMixin:
    """Mixed into *ForCausalLM models. Requires the host class to provide:

    - ``init_cache(batch_size, max_length, dtype) -> pytree of jnp arrays``
    - ``forward(input_ids, position_offset=..., cache=..., use_cache=True)``
      returning ``(logits, new_cache)`` when ``use_cache``.
    """

    generation_config: Dict[str, Any] = {}

    def _decode_jit(self, max_new_tokens: int, strategy: str,
                    temperature: float, top_k: int, top_p: float,
                    eos_token_id: int, pad_token_id: int,
                    padded: bool = False, repetition_penalty: float = 1.0,
                    min_new_tokens: int = 0):
        # per-instance cache (a class-level lru_cache would pin every model
        # instance and its compiled executables for the process lifetime)
        cache_key = (max_new_tokens, strategy, temperature, top_k, top_p,
                     eos_token_id, pad_token_id, padded,
                     repetition_penalty, min_new_tokens)
        store = self.__dict__.setdefault('_generate_jit_cache', {})
        if cache_key in store:
            return store[cache_key]

        def decode(params, frozen, buffers, ids, keep, cache, key):
            b, s = ids.shape
            total = s + max_new_tokens

            def processors(logits, seen, emit_idx):
                """Upstream logits processors (generation_utils.py):
                CTRL repetition penalty over every token already in the
                sequence, and EOS suppression until min_new_tokens."""
                if repetition_penalty != 1.0:
                    pen = jnp.where(logits > 0,
                                    logits / repetition_penalty,
                                    logits * repetition_penalty)
                    logits = jnp.where(seen, pen, logits)
                if min_new_tokens > 0 and eos_token_id >= 0:
                    v = logits.shape[-1]
                    is_eos = (jnp.arange(v) == eos_token_id)[None, :]
                    logits = jnp.where(
                        is_eos & (emit_idx < min_new_tokens), _NEG_INF,
                        logits)
                return logits

            track_seen = repetition_penalty != 1.0
            if track_seen:
                # OR-accumulate (add then >0): a plain .set() scatter has
                # undefined write order when a pad slot and a real slot
                # carry the same token id
                contrib = (keep if padded
                           else jnp.ones((b, s), bool)).astype(jnp.int32)
                seen0 = (jnp.zeros((b, self.config.vocab_size), jnp.int32)
                         .at[jnp.arange(b)[:, None], ids]
                         .add(contrib)) > 0
            else:
                seen0 = jnp.zeros((b, 1), bool)  # unused placeholder

            fwd = cached_forward(self, params, frozen, buffers)

            if padded:
                # left-padded prompts: per-sequence logical origin
                offsets = jnp.sum(keep, axis=1).astype(jnp.int32) - s  # [B]
                prefill_mask = padded_decode_mask(keep, total, jnp.int32(0),
                                                  s)
            else:
                offsets = jnp.int32(0)
                prefill_mask = None

            def step_mask(i):
                if not padded:
                    return None
                return padded_decode_mask(keep, total, jnp.int32(s) + i, 1)

            def mark_seen(seen, tok):
                if not track_seen:
                    return seen
                return seen.at[jnp.arange(b), tok].set(True)

            # prefill over the whole prompt (the slot is the literal 0:
            # `cached_forward` says why)
            logits, cache = fwd(ids, cache, offsets, 0, prefill_mask)
            key, sub = jax.random.split(key)
            nxt, nxt_logp = _next_token(
                processors(logits[:, -1], seen0, jnp.int32(0)), sub,
                strategy, temperature, top_k, top_p)
            seen = mark_seen(seen0, nxt)
            out = jnp.full((b, max_new_tokens), pad_token_id, jnp.int32)
            scores = jnp.zeros((b,), jnp.float32)
            finished = jnp.zeros((b,), jnp.bool_)

            def cond(state):
                i, _, _, _, _, finished, _, _, _ = state
                return jnp.logical_and(i < max_new_tokens,
                                       jnp.logical_not(jnp.all(finished)))

            def body(state):
                i, tok, tok_logp, out, cache, finished, scores, key, \
                    seen = state
                # emit `tok` (sampled last round) and count ITS log-prob
                tok = jnp.where(finished, pad_token_id, tok)
                out = jax.lax.dynamic_update_slice(
                    out, tok[:, None], (0, i))
                scores = scores + jnp.where(finished, 0.0, tok_logp)
                newly_done = jnp.logical_or(finished, tok == eos_token_id)
                logits, cache = fwd(tok[:, None].astype(ids.dtype), cache,
                                    offsets + s + i, jnp.int32(s) + i,
                                    step_mask(i))
                key, sub = jax.random.split(key)
                nxt, nxt_logp = _next_token(
                    processors(logits[:, -1], seen, i + 1), sub,
                    strategy, temperature, top_k, top_p)
                seen = mark_seen(seen, nxt)
                return (i + 1, nxt, nxt_logp, out, cache, newly_done,
                        scores, key, seen)

            state = (jnp.int32(0), nxt, nxt_logp, out, cache, finished,
                     scores, key, seen)
            _, _, _, out, _, _, scores, _, _ = jax.lax.while_loop(
                cond, body, state)
            return out, scores

        jitted = jax.jit(decode)
        store[cache_key] = jitted
        return jitted

    def _beam_decode_jit(self, max_new_tokens: int, num_beams: int,
                         eos_token_id: int, pad_token_id: int,
                         length_penalty: float, padded: bool = False):
        """Beam search over the static cache (upstream: paddlenlp
        generation_utils BeamSearchScorer path). All K beams of all B
        prompts decode as ONE [B*K] batch; beam reordering is a gather on
        the cache's batch dim inside the loop."""
        cache_key = ('beam', max_new_tokens, num_beams, eos_token_id,
                     pad_token_id, length_penalty, padded)
        store = self.__dict__.setdefault('_generate_jit_cache', {})
        if cache_key in store:
            return store[cache_key]
        K = num_beams
        NEG = jnp.float32(-1e9)

        def decode(params, frozen, buffers, ids, keep, cache):
            b, s = ids.shape
            total = s + max_new_tokens
            fwd = cached_forward(self, params, frozen, buffers)

            if padded:
                offsets = jnp.sum(keep, axis=1).astype(jnp.int32) - s  # [B]
                prefill_mask = padded_decode_mask(keep, total, jnp.int32(0),
                                                  s)
            else:
                offsets = jnp.zeros((b,), jnp.int32)
                prefill_mask = None

            logits, cache = fwd(ids, cache, offsets if padded else
                                jnp.int32(0), 0, prefill_mask)
            logp0 = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1)      # [B, V]
            v = logp0.shape[-1]
            scores, tok = jax.lax.top_k(logp0, K)                # [B, K]
            # expand everything beam-wise to a [B*K] batch
            cache = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, K, axis=0), cache)
            offsets_bk = jnp.repeat(offsets, K)                  # [B*K]
            keep_bk = jnp.repeat(keep, K, axis=0)
            out = jnp.full((b, K, max_new_tokens), pad_token_id, jnp.int32)
            finished = jnp.zeros((b, K), jnp.bool_)
            lengths = jnp.zeros((b, K), jnp.int32)

            def step_mask(i):
                if not padded:
                    return None
                return padded_decode_mask(keep_bk, total, jnp.int32(s) + i,
                                          1)

            def cond(state):
                i = state[0]
                finished = state[5]
                return jnp.logical_and(i < max_new_tokens,
                                       jnp.logical_not(jnp.all(finished)))

            def body(state):
                (i, tok, out, cache, scores, finished, lengths) = state
                tok = jnp.where(finished, pad_token_id, tok)     # [B, K]
                out = jax.lax.dynamic_update_slice(
                    out, tok[:, :, None], (0, 0, i))
                lengths = lengths + jnp.where(finished, 0, 1)
                finished = jnp.logical_or(finished, tok == eos_token_id)
                logits, cache = fwd(
                    tok.reshape(b * K, 1).astype(ids.dtype), cache,
                    offsets_bk + s + i, jnp.int32(s) + i, step_mask(i))
                logp = jax.nn.log_softmax(
                    logits[:, -1].astype(jnp.float32), -1)       # [B*K, V]
                logp = logp.reshape(b, K, v)
                # finished beams contribute exactly one candidate: their
                # frozen score continuing with pad
                pad_only = jnp.full((v,), NEG).at[pad_token_id].set(0.0)
                logp = jnp.where(finished[:, :, None], pad_only[None, None],
                                 logp)
                cand = scores[:, :, None] + logp                 # [B, K, V]
                scores, flat_idx = jax.lax.top_k(
                    cand.reshape(b, K * v), K)                   # [B, K]
                beam_src = flat_idx // v                         # [B, K]
                nxt = (flat_idx % v).astype(jnp.int32)
                # reorder per-beam state along the beam dim
                out = jnp.take_along_axis(out, beam_src[:, :, None], axis=1)
                finished = jnp.take_along_axis(finished, beam_src, axis=1)
                lengths = jnp.take_along_axis(lengths, beam_src, axis=1)
                flat_src = (jnp.arange(b)[:, None] * K
                            + beam_src).reshape(-1)              # [B*K]
                cache = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, flat_src, axis=0), cache)
                return (i + 1, nxt, out, cache, scores, finished, lengths)

            state = (jnp.int32(0), tok, out, cache, scores, finished,
                     lengths)
            _, _, out, _, scores, _, lengths = jax.lax.while_loop(
                cond, body, state)
            # length-normalized selection (length_penalty=0 -> raw scores)
            norm = jnp.maximum(lengths, 1).astype(jnp.float32) \
                ** jnp.float32(length_penalty)
            best = jnp.argmax(scores / norm, axis=1)             # [B]
            best_out = jnp.take_along_axis(
                out, best[:, None, None], axis=1)[:, 0]          # [B, T]
            best_score = jnp.take_along_axis(
                scores / norm, best[:, None], axis=1)[:, 0]
            return best_out, best_score

        jitted = jax.jit(decode)
        store[cache_key] = jitted
        return jitted

    def generate(self, input_ids, max_new_tokens: int = 20,
                 max_length: Optional[int] = None,
                 decode_strategy: str = 'greedy_search',
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 repetition_penalty: float = 1.0, min_new_tokens: int = 0,
                 min_length: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: Optional[int] = None, use_cache: bool = True,
                 seed: Optional[int] = None,
                 attention_mask=None, **kwargs) -> Tuple[Tensor, Tensor]:
        """Returns (generated ids [B, max_new_tokens], per-sequence score)."""
        if decode_strategy not in ('greedy_search', 'sampling', 'beam_search'):
            raise ValueError(f'unknown decode_strategy {decode_strategy!r}')
        if decode_strategy == 'beam_search' and num_beams < 1:
            raise ValueError('beam_search requires num_beams >= 1')
        if kwargs:
            raise TypeError(f'generate() got unexpected kwargs '
                            f'{sorted(kwargs)}')
        ids = to_jax(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        b, s = ids.shape
        padded = attention_mask is not None
        if padded:
            keep = to_jax(attention_mask).astype(bool)
            if keep.ndim == 1:
                keep = keep[None, :]
            if keep.shape != (b, s):
                raise ValueError(
                    f'attention_mask shape {keep.shape} does not match '
                    f'input_ids shape {(b, s)}')
        else:
            keep = jnp.ones((b, s), bool)
        if max_length is not None:
            max_new_tokens = int(max_length) - s
            if max_new_tokens <= 0:
                # upstream semantics: a prompt that already meets/exceeds
                # max_length gets NO new tokens (the old behavior silently
                # clamped to 1 and decoded past the requested total length)
                if not _warned_max_length[0]:
                    _warned_max_length[0] = True
                    warnings.warn(
                        f'generate(): prompt length {s} already meets '
                        f'max_length={int(max_length)}; returning 0 new '
                        f'tokens. Use max_new_tokens= to request a budget '
                        f'beyond the prompt.', UserWarning, stacklevel=2)
                return (Tensor(jnp.zeros((b, 0), jnp.int32)),
                        Tensor(jnp.zeros((b,), jnp.float32)))
        if min_length is not None:  # upstream name: total-length minimum
            min_new_tokens = max(int(min_length) - s, min_new_tokens)
        if decode_strategy == 'beam_search' and (
                repetition_penalty != 1.0 or min_new_tokens > 0):
            raise NotImplementedError(
                'repetition_penalty/min_new_tokens are supported for '
                'greedy_search and sampling (not beam_search)')
        cfg = getattr(self, 'config', None)
        max_pos = getattr(cfg, 'max_position_embeddings', None)
        if max_pos is not None and s + max_new_tokens > max_pos:
            raise ValueError(
                f'prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds '
                f'max_position_embeddings ({max_pos})')
        if eos_token_id is None:
            eos_token_id = getattr(cfg, 'eos_token_id', -1)
        if pad_token_id is None:
            pad_token_id = getattr(cfg, 'pad_token_id', 0)
        was_training = self.training
        self.eval()
        try:
            params, frozen, buffers = functional_state(self)
            total = s + max_new_tokens
            if decode_strategy == 'beam_search':
                # cache is beam-expanded to [B*K] inside decode after prefill
                cache = self.init_cache(b, total)
                fn = self._beam_decode_jit(int(max_new_tokens),
                                           int(num_beams), int(eos_token_id),
                                           int(pad_token_id),
                                           float(length_penalty),
                                           padded=padded)
                out, scores = fn(params, frozen, buffers, ids, keep, cache)
            else:
                cache = self.init_cache(b, total)
                key = (jax.random.PRNGKey(seed) if seed is not None
                       else framework.next_rng_key())
                fn = self._decode_jit(int(max_new_tokens), decode_strategy,
                                      float(temperature), int(top_k),
                                      float(top_p), int(eos_token_id),
                                      int(pad_token_id), padded=padded,
                                      repetition_penalty=float(
                                          repetition_penalty),
                                      min_new_tokens=int(min_new_tokens))
                out, scores = fn(params, frozen, buffers, ids, keep, cache,
                                 key)
        finally:
            if was_training:
                self.train()
        return Tensor(out), Tensor(scores)

    # ------------------------------------------------------------------
    # speculative decoding (draft-and-verify; upstream analogue:
    # PaddleNLP speculative/draft-model decoding)
    # ------------------------------------------------------------------
    def _spec_decode_jit(self, draft, max_new_tokens: int, k: int,
                         eos_token_id: int, pad_token_id: int):
        """Greedy speculative decode, batch 1: the draft model proposes k
        tokens autoregressively; the target scores all k in ONE cached
        forward and accepts the longest matching prefix plus its own next
        token — output is EXACTLY plain greedy decode, in fewer target
        passes. Stale speculative cache slots need no cleanup: the
        slot-causal decode mask hides every slot above the query position,
        and the next round overwrites them."""
        cache_key = ('spec', id(draft), max_new_tokens, k, eos_token_id,
                     pad_token_id)
        store = self.__dict__.setdefault('_generate_jit_cache', {})
        if cache_key in store:
            return store[cache_key]

        def fwd_of(model):
            def fwd(params, frozen, buffers, tok, cache, pos):
                (logits, new_cache), _ = functional_call(
                    model, params, frozen, buffers, (tok,),
                    dict(cache=cache, position_offset=pos, cache_offset=pos,
                         use_cache=True))
                return logits, new_cache
            return fwd

        fwd_t, fwd_d = fwd_of(self), fwd_of(draft)
        pad_cap = max_new_tokens + k + 1   # out buffer with round overshoot

        def decode(pt, ft, bt, pd, fd, bd, ids, cache_t, cache_d):
            s = ids.shape[1]
            logits, cache_t = fwd_t(pt, ft, bt, ids, cache_t, jnp.int32(0))
            _, cache_d = fwd_d(pd, fd, bd, ids, cache_d, jnp.int32(0))
            v = jnp.argmax(logits[0, -1]).astype(jnp.int32)  # pending token
            out = jnp.full((pad_cap,), pad_token_id, jnp.int32)
            out = out.at[0].set(v)   # the pending token is already decided
            state = (jnp.int32(1), v, out, cache_t, cache_d,
                     jnp.int32(0))  # emitted, pending, out, caches, rounds

            def cond(st):
                e, v = st[0], st[1]
                return jnp.logical_and(e < max_new_tokens,
                                       v != eos_token_id)

            def body(st):
                e, v, out, cache_t, cache_d, rounds = st
                p = jnp.int32(s) + e - 1      # logical slot of `v`

                # draft k tokens autoregressively from v
                def draft_body(j, carry):
                    cur, cache_d, drafts = carry
                    lg, cache_d = fwd_d(pd, fd, bd, cur[None, None],
                                        cache_d, p + j)
                    nxt = jnp.argmax(lg[0, -1]).astype(jnp.int32)
                    return nxt, cache_d, drafts.at[j].set(nxt)
                _, cache_d, drafts = jax.lax.fori_loop(
                    0, k, draft_body,
                    (v, cache_d, jnp.zeros((k,), jnp.int32)))

                # target scores [v, d_1..d_k] in one cached forward
                block = jnp.concatenate([v[None], drafts])[None]  # [1, k+1]
                lg, cache_t = fwd_t(pt, ft, bt, block, cache_t, p)
                choice = jnp.argmax(lg[0], axis=-1).astype(jnp.int32)

                # longest accepted draft prefix (stop acceptance at EOS:
                # everything after an emitted EOS is discarded anyway)
                match = (drafts == choice[:k]) & (drafts != eos_token_id)
                a = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
                v_new = choice[a]              # target's token after prefix

                # emit d_1..d_a then v_new at out[e : e+a+1]; positions
                # past a get pad — they are untouched future slots, so
                # the unconditional write is a no-op there
                j = jnp.arange(k + 1)
                draft_ext = jnp.concatenate([drafts, drafts[-1:]])
                emit = jnp.where(j < a, draft_ext,
                                 jnp.where(j == a, v_new, pad_token_id))
                out = out.at[e + j].set(emit, mode='drop')
                return (e + a + 1, v_new, out, cache_t, cache_d,
                        rounds + 1)

            e, _, out, _, _, rounds = jax.lax.while_loop(cond, body, state)
            out = out[:max_new_tokens]
            # blank everything after the first EOS (a round can overshoot)
            if eos_token_id >= 0:
                is_eos = out == eos_token_id
                seen = jnp.cumsum(is_eos.astype(jnp.int32))
                keep = (seen == 0) | (is_eos & (seen == 1))
                out = jnp.where(keep, out, pad_token_id)
            # e stays UNCLAMPED: acceptance stats must count final-round
            # overshoot drafts; the host clamps the emitted-token count
            return out[None], e, rounds

        jitted = jax.jit(decode)
        store[cache_key] = jitted
        return jitted

    def speculative_generate(self, draft_model, input_ids,
                             max_new_tokens: int = 20,
                             num_draft_tokens: int = 4,
                             eos_token_id: Optional[int] = None,
                             pad_token_id: Optional[int] = None):
        """Greedy decode accelerated by a smaller draft model (batch 1).
        Returns (ids [1, max_new_tokens], stats dict with `rounds`,
        `emitted`, and `acceptance_rate` = accepted drafts per proposal).
        Output is token-identical to `generate(decode_strategy=
        'greedy_search')` for ANY draft model."""
        ids = to_jax(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        if ids.shape[0] != 1:
            raise ValueError('speculative_generate is a latency '
                             'optimization for a single stream; batch '
                             f'size must be 1, got {ids.shape[0]}')
        cfg = getattr(self, 'config', None)
        if eos_token_id is None:
            eos_token_id = getattr(cfg, 'eos_token_id', -1)
        if pad_token_id is None:
            pad_token_id = getattr(cfg, 'pad_token_id', 0)
        k = int(num_draft_tokens)
        if k < 1:
            raise ValueError('num_draft_tokens must be >= 1')
        was_training = self.training
        draft_was_training = draft_model.training
        self.eval()
        draft_model.eval()
        try:
            pt, ft, bt = functional_state(self)
            pd, fd, bd = functional_state(draft_model)
            s = ids.shape[1]
            total = s + max_new_tokens + k + 2
            cache_t = self.init_cache(1, total)
            cache_d = draft_model.init_cache(1, total)
            fn = self._spec_decode_jit(draft_model, int(max_new_tokens),
                                       k, int(eos_token_id),
                                       int(pad_token_id))
            out, emitted, rounds = fn(pt, ft, bt, pd, fd, bd, ids,
                                      cache_t, cache_d)
        finally:
            if was_training:
                self.train()
            if draft_was_training:
                draft_model.train()
        rounds_i = max(int(rounds), 1)
        # each round is ONE target forward that yields 1 + a tokens; the
        # prefill token is free in both schemes, so accepted drafts total
        # emitted - 1 - rounds. Use the UNCLAMPED emitted count: a final
        # round can overshoot max_new_tokens, and those accepted drafts
        # still measure draft quality / forwards actually saved.
        e_raw = int(emitted)
        emitted_i = min(e_raw, max_new_tokens)
        accepted = max(e_raw - 1 - rounds_i, 0)
        _record_spec_stats(rounds_i, emitted_i, accepted, rounds_i * k)
        return Tensor(out), {
            'rounds': rounds_i, 'emitted': emitted_i,
            'target_forwards_saved': accepted,
            'acceptance_rate': accepted / (rounds_i * k)}


class Seq2SeqGenerationMixin:
    """Mixed into encoder-decoder models (T5). Requires the host class to
    provide, beyond ``forward(decoder_input_ids=..., encoder_output=...,
    encoder_cross_kv=..., attention_mask=..., cache=..., cache_offset=...,
    use_cache=True) -> (logits, new_cache)``:

    - ``encode(input_ids, attention_mask=None) -> encoder hidden``
    - ``cross_kv(encoder_hidden) -> per-decoder-layer (k, v)``
    - ``init_cache(batch_size, max_length, dtype) -> self-attn cache``

    The whole generate is ONE XLA program: encoder forward + per-layer
    cross-attention K/V once, then a `lax.while_loop` of cached
    single-token decoder steps (upstream: paddlenlp generation_utils'
    encoder-decoder path re-runs the encoder outside the loop too, but
    grows the cache — here the cache is static-shape)."""

    def _s2s_decode_jit(self, max_new_tokens: int, strategy: str,
                        temperature: float, top_k: int, top_p: float,
                        eos_token_id: int, pad_token_id: int,
                        start_token_id: int, min_new_tokens: int = 0):
        cache_key = (max_new_tokens, strategy, temperature, top_k, top_p,
                     eos_token_id, pad_token_id, start_token_id,
                     min_new_tokens)
        store = self.__dict__.setdefault('_generate_jit_cache', {})
        if cache_key in store:
            return store[cache_key]

        def decode(params, frozen, buffers, enc_ids, enc_keep, cache, key):
            b = enc_ids.shape[0]
            enc_h, _ = functional_method(
                self, 'encode', params, frozen, buffers, (enc_ids,),
                dict(attention_mask=enc_keep))
            cross, _ = functional_method(
                self, 'cross_kv', params, frozen, buffers, (enc_h,), {})

            def processors(logits, emit_idx):
                if min_new_tokens > 0 and eos_token_id >= 0:
                    v = logits.shape[-1]
                    is_eos = (jnp.arange(v) == eos_token_id)[None, :]
                    logits = jnp.where(
                        is_eos & (emit_idx < min_new_tokens), _NEG_INF,
                        logits)
                return logits

            def fwd(tok, cache, slot):
                (logits, new_cache), _ = functional_call(
                    self, params, frozen, buffers, (),
                    dict(decoder_input_ids=tok, encoder_output=enc_h,
                         encoder_cross_kv=cross, attention_mask=enc_keep,
                         cache=cache, cache_offset=slot, use_cache=True))
                return logits, new_cache

            start = jnp.full((b, 1), start_token_id, jnp.int32)
            logits, cache = fwd(start, cache, jnp.int32(0))
            key, sub = jax.random.split(key)
            nxt, nxt_logp = _next_token(
                processors(logits[:, -1], jnp.int32(0)), sub, strategy,
                temperature, top_k, top_p)
            out = jnp.full((b, max_new_tokens), pad_token_id, jnp.int32)
            scores = jnp.zeros((b,), jnp.float32)
            finished = jnp.zeros((b,), jnp.bool_)

            def cond(state):
                i = state[0]
                finished = state[5]
                return jnp.logical_and(i < max_new_tokens,
                                       jnp.logical_not(jnp.all(finished)))

            def body(state):
                i, tok, tok_logp, out, cache, finished, scores, key = state
                tok = jnp.where(finished, pad_token_id, tok)
                out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i))
                scores = scores + jnp.where(finished, 0.0, tok_logp)
                newly_done = jnp.logical_or(finished, tok == eos_token_id)
                logits, cache = fwd(tok[:, None], cache, jnp.int32(1) + i)
                key, sub = jax.random.split(key)
                nxt, nxt_logp = _next_token(
                    processors(logits[:, -1], i + 1), sub, strategy,
                    temperature, top_k, top_p)
                return (i + 1, nxt, nxt_logp, out, cache, newly_done,
                        scores, key)

            state = (jnp.int32(0), nxt, nxt_logp, out, cache, finished,
                     scores, key)
            _, _, _, out, _, _, scores, _ = jax.lax.while_loop(
                cond, body, state)
            return out, scores

        jitted = jax.jit(decode)
        store[cache_key] = jitted
        return jitted

    def _s2s_beam_decode_jit(self, max_new_tokens: int, num_beams: int,
                             eos_token_id: int, pad_token_id: int,
                             start_token_id: int, length_penalty: float):
        """Beam search for encoder-decoder models: the encoder runs once,
        then cross-attention K/V, the self-attn cache, and the encoder
        mask are beam-expanded to a [B*K] batch (same one-program design
        as the decoder-only beam)."""
        cache_key = ('beam', max_new_tokens, num_beams, eos_token_id,
                     pad_token_id, start_token_id, length_penalty)
        store = self.__dict__.setdefault('_generate_jit_cache', {})
        if cache_key in store:
            return store[cache_key]
        K = num_beams
        NEG = jnp.float32(-1e9)

        def decode(params, frozen, buffers, enc_ids, enc_keep, cache):
            b = enc_ids.shape[0]
            enc_h, _ = functional_method(
                self, 'encode', params, frozen, buffers, (enc_ids,),
                dict(attention_mask=enc_keep))
            cross, _ = functional_method(
                self, 'cross_kv', params, frozen, buffers, (enc_h,), {})

            def fwd(tok, cache, cross, enc_h, enc_keep, slot):
                (logits, new_cache), _ = functional_call(
                    self, params, frozen, buffers, (),
                    dict(decoder_input_ids=tok, encoder_output=enc_h,
                         encoder_cross_kv=cross, attention_mask=enc_keep,
                         cache=cache, cache_offset=slot, use_cache=True))
                return logits, new_cache

            start = jnp.full((b, 1), start_token_id, jnp.int32)
            logits, cache = fwd(start, cache, cross, enc_h, enc_keep,
                                jnp.int32(0))
            logp0 = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1)      # [B, V]
            v = logp0.shape[-1]
            scores, tok = jax.lax.top_k(logp0, K)                # [B, K]
            rep = lambda t: jnp.repeat(t, K, axis=0)
            cache = jax.tree_util.tree_map(rep, cache)
            cross_bk = jax.tree_util.tree_map(rep, cross)
            enc_h_bk = rep(enc_h)
            enc_keep_bk = rep(enc_keep)
            out = jnp.full((b, K, max_new_tokens), pad_token_id, jnp.int32)
            finished = jnp.zeros((b, K), jnp.bool_)
            lengths = jnp.zeros((b, K), jnp.int32)

            def cond(state):
                i = state[0]
                finished = state[5]
                return jnp.logical_and(i < max_new_tokens,
                                       jnp.logical_not(jnp.all(finished)))

            def body(state):
                (i, tok, out, cache, scores, finished, lengths) = state
                tok = jnp.where(finished, pad_token_id, tok)     # [B, K]
                out = jax.lax.dynamic_update_slice(
                    out, tok[:, :, None], (0, 0, i))
                lengths = lengths + jnp.where(finished, 0, 1)
                finished = jnp.logical_or(finished, tok == eos_token_id)
                logits, cache = fwd(
                    tok.reshape(b * K, 1), cache, cross_bk, enc_h_bk,
                    enc_keep_bk, jnp.int32(1) + i)
                logp = jax.nn.log_softmax(
                    logits[:, -1].astype(jnp.float32), -1).reshape(b, K, v)
                pad_only = jnp.full((v,), NEG).at[pad_token_id].set(0.0)
                logp = jnp.where(finished[:, :, None], pad_only[None, None],
                                 logp)
                cand = scores[:, :, None] + logp                 # [B, K, V]
                scores, flat_idx = jax.lax.top_k(
                    cand.reshape(b, K * v), K)                   # [B, K]
                beam_src = flat_idx // v
                nxt = (flat_idx % v).astype(jnp.int32)
                out = jnp.take_along_axis(out, beam_src[:, :, None], axis=1)
                finished = jnp.take_along_axis(finished, beam_src, axis=1)
                lengths = jnp.take_along_axis(lengths, beam_src, axis=1)
                flat_src = (jnp.arange(b)[:, None] * K
                            + beam_src).reshape(-1)              # [B*K]
                cache = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, flat_src, axis=0), cache)
                return (i + 1, nxt, out, cache, scores, finished, lengths)

            state = (jnp.int32(0), tok, out, cache, scores, finished,
                     lengths)
            _, _, out, _, scores, _, lengths = jax.lax.while_loop(
                cond, body, state)
            norm = jnp.maximum(lengths, 1).astype(jnp.float32) \
                ** jnp.float32(length_penalty)
            best = jnp.argmax(scores / norm, axis=1)             # [B]
            best_out = jnp.take_along_axis(
                out, best[:, None, None], axis=1)[:, 0]          # [B, T]
            best_score = jnp.take_along_axis(
                scores / norm, best[:, None], axis=1)[:, 0]
            return best_out, best_score

        jitted = jax.jit(decode)
        store[cache_key] = jitted
        return jitted

    def _s2s_spec_decode_jit(self, draft, max_new_tokens: int, k: int,
                             eos_token_id: int, pad_token_id: int,
                             start_token_id: int):
        """Greedy speculative decode for encoder-decoder models (batch 1):
        both models encode their own encoder states once; the decode loop
        is the decoder-only draft-and-verify algorithm with seq2seq
        forwards. Output is EXACTLY plain greedy."""
        cache_key = ('spec', id(draft), max_new_tokens, k, eos_token_id,
                     pad_token_id, start_token_id)
        store = self.__dict__.setdefault('_generate_jit_cache', {})
        if cache_key in store:
            return store[cache_key]

        def prep(model, params, frozen, buffers, enc_ids, enc_keep):
            enc_h, _ = functional_method(
                model, 'encode', params, frozen, buffers, (enc_ids,),
                dict(attention_mask=enc_keep))
            cross, _ = functional_method(
                model, 'cross_kv', params, frozen, buffers, (enc_h,), {})

            def fwd(pfb, tok, cache, slot):
                p, f, bu = pfb
                (logits, new_cache), _ = functional_call(
                    model, p, f, bu, (),
                    dict(decoder_input_ids=tok, encoder_output=enc_h,
                         encoder_cross_kv=cross, attention_mask=enc_keep,
                         cache=cache, cache_offset=slot, use_cache=True))
                return logits, new_cache
            return fwd

        pad_cap = max_new_tokens + k + 1

        def decode(pt, ft, bt, pd, fd, bd, enc_ids, enc_keep, cache_t,
                   cache_d):
            fwd_t = prep(self, pt, ft, bt, enc_ids, enc_keep)
            fwd_d = prep(draft, pd, fd, bd, enc_ids, enc_keep)
            start = jnp.full((1, 1), start_token_id, jnp.int32)
            logits, cache_t = fwd_t((pt, ft, bt), start, cache_t,
                                    jnp.int32(0))
            _, cache_d = fwd_d((pd, fd, bd), start, cache_d, jnp.int32(0))
            v = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            out = jnp.full((pad_cap,), pad_token_id, jnp.int32)
            out = out.at[0].set(v)
            state = (jnp.int32(1), v, out, cache_t, cache_d, jnp.int32(0))

            def cond(st):
                return jnp.logical_and(st[0] < max_new_tokens,
                                       st[1] != eos_token_id)

            def body(st):
                e, v, out, cache_t, cache_d, rounds = st
                # decoder slot of `v`: start token sits at 0, emitted
                # token i at slot 1 + i
                p = e                      # == 1 + (e - 1)

                def draft_body(j, carry):
                    cur, cache_d, drafts = carry
                    lg, cache_d = fwd_d((pd, fd, bd), cur[None, None],
                                        cache_d, p + j)
                    nxt = jnp.argmax(lg[0, -1]).astype(jnp.int32)
                    return nxt, cache_d, drafts.at[j].set(nxt)
                _, cache_d, drafts = jax.lax.fori_loop(
                    0, k, draft_body,
                    (v, cache_d, jnp.zeros((k,), jnp.int32)))

                block = jnp.concatenate([v[None], drafts])[None]
                lg, cache_t = fwd_t((pt, ft, bt), block, cache_t, p)
                choice = jnp.argmax(lg[0], axis=-1).astype(jnp.int32)
                match = (drafts == choice[:k]) & (drafts != eos_token_id)
                a = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
                v_new = choice[a]
                j = jnp.arange(k + 1)
                draft_ext = jnp.concatenate([drafts, drafts[-1:]])
                emit = jnp.where(j < a, draft_ext,
                                 jnp.where(j == a, v_new, pad_token_id))
                out = out.at[e + j].set(emit, mode='drop')
                return (e + a + 1, v_new, out, cache_t, cache_d,
                        rounds + 1)

            e, _, out, _, _, rounds = jax.lax.while_loop(cond, body, state)
            out = out[:max_new_tokens]
            if eos_token_id >= 0:
                is_eos = out == eos_token_id
                seen = jnp.cumsum(is_eos.astype(jnp.int32))
                keep = (seen == 0) | (is_eos & (seen == 1))
                out = jnp.where(keep, out, pad_token_id)
            # e stays UNCLAMPED: acceptance stats must count final-round
            # overshoot drafts; the host clamps the emitted-token count
            return out[None], e, rounds

        jitted = jax.jit(decode)
        store[cache_key] = jitted
        return jitted

    def speculative_generate(self, draft_model, input_ids,
                             max_new_tokens: int = 20,
                             num_draft_tokens: int = 4,
                             eos_token_id: Optional[int] = None,
                             pad_token_id: Optional[int] = None,
                             decoder_start_token_id: Optional[int] = None,
                             attention_mask=None):
        """Greedy seq2seq decode accelerated by a smaller encoder-decoder
        draft (batch 1). Both models read the same encoder inputs; output
        is token-identical to `generate(decode_strategy='greedy_search')`
        for ANY draft."""
        ids = to_jax(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        if ids.shape[0] != 1:
            raise ValueError('speculative_generate is a latency '
                             'optimization for a single stream; batch '
                             f'size must be 1, got {ids.shape[0]}')
        if attention_mask is not None:
            keep = to_jax(attention_mask).astype(jnp.int32)
            if keep.ndim == 1:
                keep = keep[None, :]
        else:
            keep = jnp.ones(ids.shape, jnp.int32)
        cfg = getattr(self, 'config', None)
        if eos_token_id is None:
            eos_token_id = getattr(cfg, 'eos_token_id', -1)
        if pad_token_id is None:
            pad_token_id = getattr(cfg, 'pad_token_id', 0)
        if decoder_start_token_id is None:
            decoder_start_token_id = getattr(cfg, 'decoder_start_token_id',
                                             0)
        k = int(num_draft_tokens)
        if k < 1:
            raise ValueError('num_draft_tokens must be >= 1')
        was_training = self.training
        draft_was_training = draft_model.training
        self.eval()
        draft_model.eval()
        try:
            pt, ft, bt = functional_state(self)
            pd, fd, bd = functional_state(draft_model)
            total = 1 + max_new_tokens + k + 2
            cache_t = self.init_cache(1, total)
            cache_d = draft_model.init_cache(1, total)
            fn = self._s2s_spec_decode_jit(
                draft_model, int(max_new_tokens), k, int(eos_token_id),
                int(pad_token_id), int(decoder_start_token_id))
            out, emitted, rounds = fn(pt, ft, bt, pd, fd, bd, ids, keep,
                                      cache_t, cache_d)
        finally:
            if was_training:
                self.train()
            if draft_was_training:
                draft_model.train()
        rounds_i = max(int(rounds), 1)
        # unclamped emitted count: final-round overshoot drafts still
        # count as accepted (see the decoder-only mixin)
        e_raw = int(emitted)
        emitted_i = min(e_raw, max_new_tokens)
        accepted = max(e_raw - 1 - rounds_i, 0)
        _record_spec_stats(rounds_i, emitted_i, accepted, rounds_i * k)
        return Tensor(out), {
            'rounds': rounds_i, 'emitted': emitted_i,
            'target_forwards_saved': accepted,
            'acceptance_rate': accepted / (rounds_i * k)}

    def generate(self, input_ids, max_new_tokens: int = 20,
                 max_length: Optional[int] = None,
                 decode_strategy: str = 'greedy_search',
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 min_new_tokens: int = 0,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: Optional[int] = None,
                 decoder_start_token_id: Optional[int] = None,
                 use_cache: bool = True, seed: Optional[int] = None,
                 attention_mask=None, **kwargs) -> Tuple[Tensor, Tensor]:
        """Returns (generated ids [B, max_new_tokens], per-sequence score).
        `input_ids` are ENCODER inputs; decoding starts from
        decoder_start_token_id (upstream T5 convention)."""
        if decode_strategy not in ('greedy_search', 'sampling',
                                   'beam_search'):
            raise ValueError(f'unknown decode_strategy {decode_strategy!r}')
        if decode_strategy == 'beam_search' and num_beams < 1:
            raise ValueError('beam_search requires num_beams >= 1')
        if decode_strategy == 'beam_search' and min_new_tokens > 0:
            raise NotImplementedError(
                'min_new_tokens is supported for greedy_search and '
                'sampling (not beam_search)')
        if kwargs:
            raise TypeError(f'generate() got unexpected kwargs '
                            f'{sorted(kwargs)}')
        ids = to_jax(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        b, s = ids.shape
        if max_length is not None:
            max_new_tokens = max(int(max_length) - 1, 1)
        if attention_mask is not None:
            keep = to_jax(attention_mask).astype(jnp.int32)
            if keep.ndim == 1:
                keep = keep[None, :]
            if keep.shape != (b, s):
                raise ValueError(
                    f'attention_mask shape {keep.shape} does not match '
                    f'input_ids shape {(b, s)}')
        else:
            keep = jnp.ones((b, s), jnp.int32)
        cfg = getattr(self, 'config', None)
        if eos_token_id is None:
            eos_token_id = getattr(cfg, 'eos_token_id', -1)
        if pad_token_id is None:
            pad_token_id = getattr(cfg, 'pad_token_id', 0)
        if decoder_start_token_id is None:
            decoder_start_token_id = getattr(cfg, 'decoder_start_token_id', 0)
        was_training = self.training
        self.eval()
        try:
            params, frozen, buffers = functional_state(self)
            cache = self.init_cache(b, 1 + max_new_tokens)
            if decode_strategy == 'beam_search':
                fn = self._s2s_beam_decode_jit(
                    int(max_new_tokens), int(num_beams), int(eos_token_id),
                    int(pad_token_id), int(decoder_start_token_id),
                    float(length_penalty))
                out, scores = fn(params, frozen, buffers, ids, keep, cache)
            else:
                key = (jax.random.PRNGKey(seed) if seed is not None
                       else framework.next_rng_key())
                fn = self._s2s_decode_jit(
                    int(max_new_tokens), decode_strategy, float(temperature),
                    int(top_k), float(top_p), int(eos_token_id),
                    int(pad_token_id), int(decoder_start_token_id),
                    min_new_tokens=int(min_new_tokens))
                out, scores = fn(params, frozen, buffers, ids, keep, cache,
                                 key)
        finally:
            if was_training:
                self.train()
        return Tensor(out), Tensor(scores)
