"""Slot-pooled KV cache: N fixed slots x max_length, allocated ONCE as
one stacked array per layer leaf, and donated through every program that
returns it.

vLLM's PagedAttention (Kwon et al. SOSP'23) pools KV memory in small
blocks behind an address-translation step; on TPU the same "requests
share one preallocated cache" idea wants STATIC shapes, so the pool here
is the coarser fixed-slot variant: per layer one K and one V leaf of
[num_slots, max_length, H_kv, D] (exactly the model's own `init_cache`
layout with the batch dim reinterpreted as slots). A slot is the unit of
admission: alloc on prefill, free on retirement, and the decode step
runs over ALL slots every iteration with per-slot positions — freed
slots are simply masked until a new request overwrites them, so
admission never recompiles anything.

Representation: `SlotPool.rows` IS that stacked pytree — 2 x layers
device buffers, whatever the slot count. The decode and speculation
programs take it as an argument, carry it through their scan and return
it, donated, so the pool is updated in place: one copy of it lives on
the device and a round moves only the rows the attention reads. One
buffer per leaf is what lets donation alias it into the scan's carry: N
per-slot arrays cannot alias into one, and a program that stacks them
copies the whole pool in and out every round (on the v5e 26 ms of an
89 ms round, and the pool held twice — PERF.md, PR 25).

Single-slot traffic goes through three small compiled programs, each
with the slot index TRACED, so each compiles once for all slots:

- `set_row` seats a prefilled row: the pool DONATED, a
  `dynamic_update_slice` of one row in place (with the dtype cast, so a
  float32 slab lands in a bf16 pool). The prefill / chunk-prefill
  programs themselves still take and return ONE row, undonated, so a
  prefill that dies costs one request, never the pool;
- `copy_slot` copies row src over row dst in place (the prefix-cache hit
  path), the pool donated;
- `row` slices one row out (chunked prefill's source row), undonated.

A donated seat or copy that dies may have consumed the pool: the engine
treats it as it treats a failed donated decode (`reset_rows`, then the
error leaves `step()`). Their jitted names hold `prefill`: seating is
part of what a prefill costs, and the traced device time books it there.

Layout (PR 33): the device's default layout for a leaf whose head size
is not a whole number of 128 lanes is a compact one with the ROWS minor,
and the decode block's scan reads heads x head size in the minor tile —
so the block relaid such a leaf on its way in and again on its way out,
every round (lfm2's K and V, 64 wide; mimo's K, 192 wide: 12-14% of a
round on the v5e). Such a leaf asks for a layout of its own
(`wants_own_layout`: the leaf's shape and the backend, nothing else);
which one is the compiler's answer: the engine compiles the whole-length
decode block with `Layout.AUTO` on those leaves, the pool reads the
chosen formats off the compiled program (`adopt_formats`), holds its
leaves in them from then on, and every other program that takes or
returns the pool is compiled to them (`programs.PoolIO`). A prefill
returns its ROW in the default layout; the seat program relays that one
slot as it writes it. On any other backend nothing is asked and every
program is the one it was. What a leaf SAYS of its layout
(`leaf.format`) is not to be read once programs are loaded from jax's
compile cache (`programs.store._LayoutsOnTrust`): `formats` is the book.

Where the decode block's attention is a Mosaic kernel (PR 38 latent
rows, PR 40 K and V by head: `ops/pallas_kernels.py`), the kernel takes
the leaf AS IT IS HELD, so a K or V leaf ends in the ROW-MAJOR layout
`0,1,2,3` with the heads in the sublanes and the head size in the lanes
— which is also what the `kv_write` scatter wants — and nothing of a
leaf is copied (`tests/test_aot_decode.py`). A leaf of at most 128 lanes
a head goes in as its LINES `[slot, row x H_kv, D]`, a bitcast of that
layout; a wider one (mimo's K) as the 4-D leaf, its tile reshaped to
lines inside the kernel. What a row of ONE leaf costs on the device
(AOT for a v5e, PR 40): whole lanes cost what they are — trinity-mini
`T(4,128)`, the default, 4 x 128 x 4 = 2,048 B; a head size under a
lane tile pads to it — lfm2 `T(8,128)`, 8 heads x 64 -> 128 lanes =
4,096 B where 2,048 are logical; one over it pads to the next — mimo's
full K `T(4,128)`, 4 heads x 192 -> 256 lanes = 4,096 B against 3,072
(its V, 128 wide, 2,048 in the default): the same bytes the parent of
PR 40 held. Views that would pad nothing do not exist here: `[slot,
row, H_kv x D]` is no layout of the 4-D leaf, the lines of a 192-wide
leaf are no bitcast of it (the block copied mimo's K whole every
sub-step), and a heads-major leaf is relaid in and out by the scatter.
The kernel walks these device bytes (`entry_bytes`); the benchmark's
rooflines count the logical ones.

Prefill shapes are length-bucketed: a prompt of length s runs at the
smallest bucket >= s (right-padded; pad KV lands above the live
position, where the slot-causal decode mask hides it until the slot's
own decode overwrites it — the same stale-slot argument as speculative
decoding). Buckets bound the number of prefill compilations to
O(len(buckets)), not O(distinct prompt lengths).

Paged mode (ISSUE 16): `PagedSlotPool` is the finer-grained variant —
the true PagedAttention layout under the same static-shape discipline.
KV storage is ONE [num_pages, page_size, H_kv, D] buffer per layer
leaf; a slot owns a page LIST (a row of the [num_slots,
pages_per_slot] page table, host-side), pages come from a free list,
and page id 0 is a reserved NULL page: unreserved table entries point
at it, so out-of-range program writes land in junk that no mask ever
attends. Sharing is per-PAGE with refcounts: the prefix cache pins a
prefix's pages once (`hold_pages`) and every live request that hits it
attaches the same page ids read-only (`attach_prefix`); the only
write-into-shared-page case (a full-prompt hit re-forwarding its last
token) is copy-on-write split via `ensure_exclusive`. The compiled
programs see (pages, scales, table) and translate addresses with
`gather_pages` / `scatter_pages` — gather reconstructs the SAME
[N, max_length, H, D] contiguous view the row pool holds, so the
decode math (and greedy output) is bit-identical; scatter writes back
only the pages overlapping the written span, so settled int8 pages are
never requantized. Optional int8 storage keeps per-(page, head) absmax
scales (quantization.kv_page_scales semantics) alongside the pages.
"""
from __future__ import annotations

import bisect
import collections
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nlp.generation import (latent_layers as _latent_layers,
                              ring_layers as _ring_layers,
                              state_layers as _state_layers)

_tree = jax.tree_util


class PromptTooLongError(ValueError):
    """A prompt is longer than the largest prefill bucket (and therefore
    than max_length). Subclasses ValueError so pre-ISSUE-16 callers that
    caught ValueError keep working; typed so admission layers can
    distinguish 'request can never fit' from other validation errors."""


class PagePoolExhausted(RuntimeError):
    """No free KV pages for a reservation. Subclasses RuntimeError so it
    rides the engine's existing requeue-on-exhaustion path: the request
    is NOT failed — it goes back to the queue front and admission waits
    for retirements (or prefix-cache evictions) to return pages."""


class PoolLostError(RuntimeError):
    """A DONATED single-slot pool program (seat, copy) died mid-call.
    The pool it was given may be gone, so the engine has rebuilt it and
    every seated request has lost its KV: unlike a failed prefill this
    is not one request's failure — it leaves `engine.step()` like a
    failed donated decode round, with the device error as its cause."""


def default_buckets(max_length: int, smallest: int = 8) -> Tuple[int, ...]:
    """Powers of two from `smallest` up to max_length (max_length always
    included so every admissible prompt has a bucket)."""
    out: List[int] = []
    b = smallest
    while b < max_length:
        out.append(b)
        b *= 2
    out.append(max_length)
    return tuple(out)


def _leaf_bytes(tree) -> int:
    return sum(int(getattr(leaf, 'nbytes', 0) or
                   leaf.size * leaf.dtype.itemsize)
               for leaf in _tree.tree_leaves(tree))


_LANES = 128


def wants_own_layout(shape, backend: str) -> bool:
    """THE rule, from the leaf alone: a K or V leaf `[slot, row, H_kv,
    D]` whose head size is not a whole number of lanes, or a latent leaf
    `[slot, row, C]` whose width is not, on a TPU. There the device's
    default layout has the rows minor and the decode block's scan does
    not, so the block would relay the whole leaf at both its edges; a
    leaf of whole lanes arrives as the scan reads it, however few its
    heads (a 4 x 128 leaf has a tile of 4 sublanes)."""
    return (backend == 'tpu' and len(shape) in (3, 4)
            and shape[-1] % _LANES != 0)


def format_bytes(spec, fmt) -> int:
    """Bytes of a leaf of `spec` (shape, dtype) on the device in format
    `fmt`, padding included: the layout's first tile covers the minor-
    most dimensions, each rounded up to a multiple of it. None is the
    default layout, booked at the leaf's logical size."""
    dims = list(spec.shape)
    tiling = fmt.layout.tiling if fmt is not None else ()
    if tiling:
        tile = tiling[0]
        minor = fmt.layout.major_to_minor[len(dims) - len(tile):]
        for d, t in zip(minor, tile):
            dims[d] = -(-dims[d] // t) * t
    return int(np.prod(dims, dtype=np.int64)) * np.dtype(spec.dtype).itemsize


def layout_name(fmt) -> str:
    """`default`, or the layout's dimensions from major to minor and
    its tiling: `0,1,2,3:T(8,128)`."""
    if fmt is None:
        return 'default'
    lay = fmt.layout
    return ','.join(map(str, lay.major_to_minor)) + ':' + ''.join(
        f'T({",".join(map(str, t))})' for t in lay.tiling or ())


def _normalize_buckets(buckets, max_length: int) -> Tuple[int, ...]:
    out = tuple(sorted(set(
        int(b) for b in (buckets or default_buckets(max_length))
        if int(b) <= max_length)))
    if not out:
        raise ValueError('no prefill bucket <= max_length')
    return out


def _bucket_for(buckets: Tuple[int, ...], length: int,
                max_length: int) -> int:
    """Smallest bucket >= length; PromptTooLongError past the largest.
    `bisect` over the sorted bucket tuple — this runs once per submit
    AND once per scheduler admission pass, so it must not be a linear
    scan of a long custom bucket list."""
    i = bisect.bisect_left(buckets, length)
    if i == len(buckets):
        raise PromptTooLongError(
            f'prompt length {length} exceeds the largest prefill '
            f'bucket {buckets[-1]} (max_length {max_length})')
    return buckets[i]


class SlotPool:
    """Owns the stacked KV pool + the slot free list.

    `rows` is whatever `model.init_cache(num_slots, max_length)` returns,
    one entry a layer: a (K, V) pair of `[num_slots, max_length, H, D]`
    where the layer attends, or recurrent slot STATE — one leaf
    `[num_slots, ...]` with no row axis, or a pytree of such leaves that
    is no tuple — where it keeps its past some other way (`nlp/lfm2.py`'s
    conv layers, `nlp/ling3.py`'s KDA layers: `state_layers`). Seating,
    copying and slicing a slot map over axis 0 of any leaf; what the
    pool says of ROWS (`written_rows`, buckets, `max_length`) is about
    the (K, V) entries alone. A state is not a row that a mask can hide
    part of: it stands at ONE position, so whoever seats it (the engine's
    prefill) gives it whole, and nothing may share or rewind it. A
    LATENT entry (`latent_layers`: a pair of `[num_slots, max_length,
    C]` leaves, rows and no heads) is a row entry like K and V, at its
    own row bytes.
    """

    def __init__(self, model, num_slots: int, max_length: int,
                 dtype=None, buckets: Optional[Sequence[int]] = None):
        if num_slots < 1:
            raise ValueError('num_slots must be >= 1')
        if max_length < 2:
            raise ValueError('max_length must be >= 2')
        self.num_slots = int(num_slots)
        self.max_length = int(max_length)
        # THE pool: its leaves are the device buffers (the benchmark
        # frees them through this attribute once its window has closed)
        self.rows = model.init_cache(self.num_slots, self.max_length, dtype)
        self._pool_spec = _tree.tree_map(
            lambda c: jax.ShapeDtypeStruct(c.shape, c.dtype), self.rows)
        self.row_spec = _tree.tree_map(
            lambda c: jax.ShapeDtypeStruct((1,) + tuple(c.shape[1:]),
                                           c.dtype), self.rows)
        self.pool_bytes = _leaf_bytes(self.rows)
        self.row_bytes = self.pool_bytes // self.num_slots
        # the entries that are state and not (K, V), and the bytes of
        # ONE slot's state over all of them
        self.state_layers = _state_layers(self.rows)
        self.state_bytes = _leaf_bytes(
            [self.rows[i] for i in self.state_layers]) // self.num_slots
        # the (K, V) entries that are rings of fewer rows than the slot
        # (a window layer that keeps its window): like a state, a ring
        # stands at one position, and the engine seats it whole
        self.ring_layers = _ring_layers(self.rows, self.max_length)
        self.stands_at_one_position = bool(self.state_layers
                                           or self.ring_layers)
        # the entries whose rows have no heads (latent attention), and
        # the LOGICAL bytes of one row over all of them, whatever lanes
        # the device pads (`entry_bytes` books those)
        self.latent_layers = _latent_layers(self.rows)
        self.latent_row_bytes = sum(
            leaf.shape[2] * leaf.dtype.itemsize
            for i in self.latent_layers for leaf in self.rows[i])
        # one entry per leaf of `rows`, in tree order. `own_layout`:
        # `Format(Layout.AUTO)` where the rule asks for a layout of the
        # leaf's own, None elsewhere — all None off a TPU. `formats`:
        # the format each leaf is HELD in, None = the device's default;
        # all None until `adopt_formats`
        self.own_layout = self.asks(jax.default_backend())
        self.formats = [None] * len(self.own_layout)
        # the single-slot programs, enrolled in the program store like
        # the engine's own (a warm replica loads them); seat and copy
        # take the pool donated; all three take it, and the two return
        # it, in `formats`
        from .. import programs as _programs
        store = _programs.get_store()
        self.traces = collections.Counter()     # python-level traces
        self._seat_jit = store.wrap_jit(
            self._prefill_seat_row, name='serving.prefill_seat_row',
            kind='serving', donate_argnums=(0,), pool_io=self.pool_io(0, ()))
        self._copy_jit = store.wrap_jit(
            self._prefill_copy_row, name='serving.prefill_copy_row',
            kind='serving', donate_argnums=(0,), pool_io=self.pool_io(0, ()))
        self._slice_jit = store.wrap_jit(
            self._prefill_slice_row, name='serving.prefill_slice_row',
            kind='serving', pool_io=self.pool_io(0, None))
        self.buckets = _normalize_buckets(buckets, self.max_length)
        self._free = sorted(range(self.num_slots), reverse=True)
        # per-slot high-water mark of WRITTEN rows (vs the max_length
        # rows a slot always allocates) — the stranded-capacity figure
        # the paged A/B reports utilization against
        self._written = [0] * self.num_slots
        # chunked-prefill config rides the pool so stats()/debuggers see
        # the full prefill geometry in one place (the engine sets it)
        self.prefill_chunk_tokens: Optional[int] = None
        # copy-surface accounting
        self._row_writes = 0
        self._row_copies = 0
        self._copied_bytes = 0

    # -- slot lifecycle ----------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.used_count / self.num_slots

    def alloc(self) -> int:
        """Claim the lowest free slot index; raises when full (the
        scheduler checks free_count before admitting)."""
        if not self._free:
            raise RuntimeError('slot pool exhausted')
        return self._free.pop()

    def free(self, slot: int):
        if not 0 <= slot < self.num_slots:
            raise ValueError(f'slot {slot} out of range')
        if slot in self._free:
            raise ValueError(f'slot {slot} is already free')
        self._free.append(slot)
        self._free.sort(reverse=True)
        self._written[slot] = 0

    @property
    def written_rows(self) -> int:
        """Rows holding a real token, over the slots in use."""
        return sum(self._written)

    def note_written(self, slot: int, rows) -> None:
        """Record that `slot` holds live KV through row `rows` (the
        engine calls this at prefill and after each decode round); the
        high-water mark feeds the stranded-capacity stats."""
        r = min(int(rows), self.max_length)
        if r > self._written[slot]:
            self._written[slot] = r

    # -- prefill bucketing -------------------------------------------------
    def bucket_for(self, length: int) -> int:
        """Smallest bucket >= length; `PromptTooLongError` (a ValueError)
        past the largest bucket."""
        return _bucket_for(self.buckets, length, self.max_length)

    # -- the compiled single-slot programs --------------------------------
    def _prefill_seat_row(self, pool, row, slot):
        """pool[slot] = row, cast to the pool's dtype; `slot` traced."""
        self.traces['prefill_seat_row'] += 1
        return _tree.tree_map(
            lambda p, r: jax.lax.dynamic_update_slice_in_dim(
                p, r.astype(p.dtype), slot, axis=0), pool, row)

    def _prefill_copy_row(self, pool, src, dst):
        """pool[dst] = pool[src]; both traced."""
        self.traces['prefill_copy_row'] += 1
        return _tree.tree_map(
            lambda p: jax.lax.dynamic_update_slice_in_dim(
                p, jax.lax.dynamic_slice_in_dim(p, src, 1, axis=0), dst,
                axis=0), pool)

    def _prefill_slice_row(self, pool, slot):
        """pool[slot] as a row pytree (leaves [1, ...]); `slot` traced."""
        self.traces['prefill_slice_row'] += 1
        return _tree.tree_map(
            lambda p: jax.lax.dynamic_slice_in_dim(p, slot, 1, axis=0),
            pool)

    # -- the cache pytree --------------------------------------------------
    @property
    def cache(self):
        """The decode program's pool argument and result: `rows`."""
        return self.rows

    @cache.setter
    def cache(self, new_pool):
        self.rows = new_pool

    def row(self, slot: int):
        """A copy of one slot's row (leaves [1, max_length, ...])."""
        return self._slice_jit(self.rows, np.int32(slot))

    def set_row(self, slot: int, row):
        """Seat a batch-1 cache (leaves [1, max_length, ...]) as the
        pool's row `slot` — the hand-off from prefill to the pooled
        decode step, dtype-cast so a float32 slab lands in a bf16 pool.
        THE single-slot write surface: one row written in place."""
        self.rows = self._seat_jit(self.rows, row, np.int32(slot))
        self._row_writes += 1

    def copy_slot(self, src: int, dst: int):
        """Copy row `src` over row `dst` in place (the prefix-cache hit
        path: a retained prefix row becomes the new request's KV floor;
        stale positions above the prefix are masked until the request's
        own prefill/decode overwrites them)."""
        self.rows = self._copy_jit(self.rows, np.int32(src),
                                   np.int32(dst))
        self._row_copies += 1
        self._copied_bytes += self.row_bytes

    def reset_rows(self):
        """Re-zero the pool (fresh buffers, in `formats`). The
        donation-failure recovery path: if a DONATED program dies
        mid-call the pool it was given may already be invalidated, so
        the engine rebuilds it rather than pass dead buffers on."""
        self.rows = self._held(_tree.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._pool_spec))

    # -- the layout the leaves are held in ---------------------------------
    def pool_io(self, arg: int, result, chooses: bool = False):
        """What a program that takes this pool as argument `arg`, and
        returns it at `result`, declares to `wrap_jit`: the formats the
        leaves are held in when it is compiled — or, for THE program
        whose compile `chooses` them, `own_layout`'s AUTO."""
        from ..programs import PoolIO
        return (PoolIO(arg, result, (lambda: self.own_layout) if chooses
                       else (lambda: self.formats)),)

    def _held(self, rows):
        """`rows` with every leaf in its format: a leaf of the default
        format as it is, another put into its own, one leaf at a time."""
        leaves, treedef = _tree.tree_flatten(rows)
        return treedef.unflatten(
            [leaf if fmt is None else jax.device_put(leaf, fmt)
             for leaf, fmt in zip(leaves, self.formats)])

    def asks(self, backend: str, sharding=None) -> list:
        """`own_layout` as the rule gives it on `backend`: AUTO on the
        leaf's own device (or `sharding`: a described one) for every K,
        V or latent leaf that `wants_own_layout`, None for the others
        and for a state leaf."""
        from jax.experimental.layout import Format, Layout
        return [Format(Layout.AUTO, sharding or leaf.sharding)
                if i not in self.state_layers
                and wants_own_layout(leaf.shape, backend) else None
                for i, entry in enumerate(self.rows)
                for leaf in _tree.tree_leaves(entry)]

    def adopt_formats(self, taken, returned):
        """Hold the leaves that ask for a layout of their own
        (`own_layout`) in the formats a compiled program `taken` them
        in — the whole-length decode block, compiled with AUTO there:
        its `input_formats` of the pool argument, and `returned` its
        `output_formats` of the pool result. What the pool holds is put
        into them here, once; every program that takes the pool
        afterwards is compiled to them."""
        self.book_formats(taken, returned)
        self.rows = self._held(self.rows)

    def book_formats(self, taken, returned):
        """`adopt_formats`' bookkeeping: `formats` of the asking leaves
        become what the program `taken` them in. The pool is one
        donated buffer a leaf, so that must be what it `returned`."""
        taken, returned = (
            _tree.tree_leaves(fmts, is_leaf=lambda f: f is None)
            for fmts in (taken, returned))
        for i, asked in enumerate(self.own_layout):
            if asked is None:
                continue
            if taken[i] != returned[i]:
                raise ValueError(
                    f'pool leaf {i} is taken as {taken[i]} and returned '
                    f'as {returned[i]}: one donated buffer cannot be both')
            self.formats[i] = taken[i]

    @property
    def own_layout_leaves(self) -> int:
        """Leaves held in a layout of their own."""
        return sum(fmt is not None for fmt in self.formats)

    def _capacity_stats(self) -> dict:
        """Allocated vs written rows over USED slots: the row pool
        allocates max_length rows per seated request no matter how few
        it writes, and `stranded_rows` is exactly that waste (the paged
        A/B's honesty metric; ~0 for the paged pool by construction)."""
        used = [s for s in range(self.num_slots) if s not in self._free]
        allocated = sum(self.allocated_rows(s) for s in used)
        written = sum(self._written[s] for s in used)
        return {
            'allocated_rows': allocated,
            'written_rows': written,
            'stranded_rows': allocated - written,
            'row_utilization': written / allocated if allocated else 1.0,
            'slot_written_rows': {s: self._written[s] for s in used},
        }

    def allocated_rows(self, slot: int) -> int:
        """KV rows reserved for `slot` while seated (a whole row here;
        the paged pool overrides with its page-granular figure)."""
        return self.max_length

    def _entries(self):
        """-> (geometry, its leaves' names, the leaves, their formats)
        per entry of the pool. The geometry is `rows x heads x (K width
        + V width)` of a (K, V) entry, `rows x latent(widths)` of a
        latent one (its leaves `c`, the latent, and `r`, the shared
        rotary key), `state` of a state leaf: one name for layers that
        keep the same (whatever leaves it is made of)."""
        formats = iter(self.formats)
        for i, entry in enumerate(self._pool_spec):
            if i in self.state_layers:
                leaves = _tree.tree_leaves(entry)
                yield ('state', tuple(map(str, range(len(leaves)))), leaves,
                       [next(formats) for _ in leaves])
                continue
            a, b = entry
            if i in self.latent_layers:
                name, leaves = (f'{a.shape[1]}xlatent({a.shape[2]}+'
                                f'{b.shape[2]})'), 'cr'
            else:
                name, leaves = (f'{a.shape[1]}x{a.shape[2]}x({a.shape[3]}+'
                                f'{b.shape[3]})'), 'KV'
            yield name, leaves, (a, b), (next(formats), next(formats))

    def entry_bytes(self) -> dict:
        """The pool's bytes ON THE DEVICE by entry geometry: every leaf
        in the format it is held in, the lanes that format pads
        included (a K leaf 64 wide held in tiles of 128 lanes is twice
        its logical size)."""
        out = collections.Counter()
        for name, _, leaves, formats in self._entries():
            out[name] += sum(map(format_bytes, leaves, formats))
        return dict(out)

    def entry_layouts(self) -> dict:
        """The layout held, by entry geometry: `default`, or the
        layout of the entry's leaves (`K ..., V ...` where a layer's
        two differ; `c ..., r ...` of a latent entry)."""
        out = {}
        for name, leaves, _, formats in self._entries():
            names = [layout_name(f) for f in formats]
            out[name] = names[0] if len(set(names)) == 1 else \
                ', '.join(f'{leaf} {n}' for leaf, n in zip(leaves, names))
        return out

    def stats(self) -> dict:
        return {'num_slots': self.num_slots, 'max_length': self.max_length,
                'used': self.used_count, 'free': self.free_count,
                'buckets': list(self.buckets),
                'prefill_chunk_tokens': self.prefill_chunk_tokens,
                'row_bytes': self.row_bytes,
                'pool_bytes': self.pool_bytes,
                'state_layers': len(self.state_layers),
                'state_bytes': self.state_bytes,
                'ring_layers': len(self.ring_layers),
                'latent_layers': len(self.latent_layers),
                'latent_row_bytes': self.latent_row_bytes,
                'entry_bytes': self.entry_bytes(),
                'entry_layouts': self.entry_layouts(),
                'row_writes': self._row_writes,
                'row_copies': self._row_copies,
                'copied_bytes': self._copied_bytes,
                **self._capacity_stats()}


# ---------------------------------------------------------------------------
# paged pool (ISSUE 16)
# ---------------------------------------------------------------------------

def gather_pages(pages, table, scales=None, out_dtype=None):
    """Address-translate the page pool into the decode-facing contiguous
    view: leaves [num_pages, ps, H, D] indexed by `table` [N, P] become
    [N, P*ps, H, D] = [N, max_length, H, D] — the SAME shape (and, for
    the unquantized path, the same bits) the row pool feeds the decode
    scan, so the attention math downstream is bit-identical. With `scales` (per-(page, head) int8 scales, leaves
    [num_pages, H]) the gather dequantizes in the same expression.
    Traced inside every paged program."""
    from ..quantization import kv_dequantize_page
    n, p = table.shape

    def g(leaf, s=None):
        out = leaf[table]                       # [N, P, ps, H, D]
        if s is not None:
            out = kv_dequantize_page(out, s[table],
                                     out_dtype or jnp.float32)
        out = out.reshape(n, p * leaf.shape[1], *leaf.shape[2:])
        return out if out_dtype is None else out.astype(out_dtype)

    if scales is None:
        return _tree.tree_map(g, pages)
    return _tree.tree_map(g, pages, scales)


def scatter_pages(pages, table, contig, start, length: int,
                  page_size: int, scales=None, floor=None):
    """Write the span [start, start+length) of the contiguous view back
    into the page pool — ONLY the pages overlapping the span. `start` is
    per-slot traced [N]; `length` is static, so the window count is
    static: a length-L span can straddle at most (L+ps-2)//ps + 1 pages
    at any alignment. Windows outside a slot's actual span are redirected
    to the NULL page (id 0) so untouched pages are never rewritten —
    which is what keeps settled int8 pages from requantization drift,
    and makes the unquantized path an exact-value (bit-identical)
    writeback. With `scales`, each touched page is (re)quantized at its
    fresh per-(page, head) absmax scale. `floor` (traced [N], rows)
    additionally redirects pages that end at or below it — the chunk
    programs pass the prefix-attach boundary so a tail-shifted window
    that re-forwards already-settled rows can never rewrite a SHARED
    page. Returns (pages, scales)."""
    from ..quantization import kv_page_scales, kv_quantize_page
    n, p = table.shape
    first = start // page_size                  # [N]
    nwin = (length + page_size - 2) // page_size + 1

    @jax.named_scope('kv_write')
    def upd(leaf, s_leaf, cont):
        for w in range(nwin):
            idx = jnp.clip(first + w, 0, p - 1)             # [N]
            pid = jnp.take_along_axis(table, idx[:, None], 1)[:, 0]
            touched = ((idx * page_size < start + length)
                       & ((idx + 1) * page_size > start))
            if floor is not None:
                touched &= (idx + 1) * page_size > floor
            pid = jnp.where(touched, pid, 0)    # junk -> null page
            sl = jax.vmap(
                lambda c, i: jax.lax.dynamic_slice_in_dim(
                    c, i * page_size, page_size, axis=0))(
                        cont, idx)              # [N, ps, H, D]
            if s_leaf is not None:
                sc = kv_page_scales(sl)
                leaf = leaf.at[pid].set(kv_quantize_page(sl, sc))
                s_leaf = s_leaf.at[pid].set(sc)
            else:
                leaf = leaf.at[pid].set(sl.astype(leaf.dtype))
        return leaf, s_leaf

    if scales is None:
        out = _tree.tree_map(lambda lf, ct: upd(lf, None, ct)[0],
                             pages, contig)
        return out, None
    flat_p, treedef = _tree.tree_flatten(pages)
    flat_s = treedef.flatten_up_to(scales)
    flat_c = treedef.flatten_up_to(contig)
    new_p, new_s = [], []
    for lf, s, ct in zip(flat_p, flat_s, flat_c):
        a, b = upd(lf, s, ct)
        new_p.append(a)
        new_s.append(b)
    return (_tree.tree_unflatten(treedef, new_p),
            _tree.tree_unflatten(treedef, new_s))


class PageHold:
    """A reference-counted pin on a set of pages (the prefix cache's
    retained resource in paged mode): the first `kv_len` rows across
    `pages` are a prompt prefix's prefill KV. Created by
    `PagedSlotPool.hold_pages`, released by `release_hold` — the pages
    survive the originating slot's free for as long as the hold lives."""

    __slots__ = ('pages', 'kv_len', 'released')

    def __init__(self, pages: Tuple[int, ...], kv_len: int):
        self.pages = tuple(int(p) for p in pages)
        self.kv_len = int(kv_len)
        self.released = False

    def __len__(self):
        return len(self.pages)

    def __repr__(self):
        return (f'PageHold(pages={len(self.pages)}, kv_len={self.kv_len}'
                f'{", released" if self.released else ""})')


class PagedSlotPool:
    """Page-table KV pool: fixed-size pages, per-slot page lists,
    free-list allocation, copy-on-write refcounts (vLLM's PagedAttention
    memory manager under TPU static shapes).

    Storage is `model.init_cache(num_pages, page_size)` — per-layer
    (K, V) leaves [num_pages, page_size, H_kv, D] — so any model
    honoring the init_cache contract pools unchanged. Page id 0 is the
    reserved NULL page (junk sink for out-of-span program writes; never
    allocated, never attended unmasked). With `quant='int8'` the pages
    are int8 with per-(page, head) float32 absmax scales; gather
    dequantizes, scatter requantizes touched pages only.

    Admission is reservation-based: `reserve(slot, total_len)` claims
    every page the request can touch (prompt + new tokens + speculation
    headroom) up front, so a seated request can never die of page
    exhaustion mid-decode — exhaustion surfaces at admission as
    `PagePoolExhausted` and the engine requeues.
    """

    # K and V of one geometry only: a state leaf has no rows to page,
    # a ring is a leaf of another length (the engine refuses such a
    # model before it builds this pool)
    state_layers = ()
    latent_layers = ()
    latent_row_bytes = 0
    state_bytes = 0
    ring_layers = ()
    stands_at_one_position = False

    def __init__(self, model, num_slots: int, max_length: int,
                 dtype=None, buckets: Optional[Sequence[int]] = None,
                 *, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 quant: Optional[str] = None):
        if num_slots < 1:
            raise ValueError('num_slots must be >= 1')
        if max_length < 2:
            raise ValueError('max_length must be >= 2')
        if page_size < 1:
            raise ValueError('page_size must be >= 1')
        if max_length % page_size != 0:
            raise ValueError(
                f'max_length {max_length} must be a multiple of '
                f'page_size {page_size} (the page table is dense)')
        if quant not in (None, 'int8'):
            raise ValueError(f"kv quant mode {quant!r} not supported "
                             f"(None or 'int8')")
        self.num_slots = int(num_slots)
        self.max_length = int(max_length)
        self.page_size = int(page_size)
        self.pages_per_slot = self.max_length // self.page_size
        # +1: page 0 is the null page — a full-capacity default budget
        # still seats num_slots max-length requests
        self.num_pages = int(num_pages) if num_pages is not None else \
            self.num_slots * self.pages_per_slot + 1
        if self.num_pages < self.pages_per_slot + 1:
            raise ValueError(
                f'num_pages {self.num_pages} cannot seat even one '
                f'max-length request ({self.pages_per_slot} pages + '
                f'the null page)')
        self.quant = quant
        base = model.init_cache(self.num_pages, self.page_size, dtype)
        for leaf in _tree.tree_leaves(base):
            if leaf.ndim != 4:
                raise ValueError(
                    'PagedSlotPool requires [B, L, H, D] KV leaves, got '
                    f'shape {tuple(leaf.shape)}')
        self.compute_dtype = _tree.tree_leaves(base)[0].dtype
        if quant == 'int8':
            self.pages = _tree.tree_map(
                lambda c: jnp.zeros(c.shape, jnp.int8), base)
            self.scales = _tree.tree_map(
                lambda c: jnp.ones((c.shape[0], c.shape[2]),
                                   jnp.float32), base)
        else:
            self.pages = base
            self.scales = None
        # the row-shaped spec the (reused) whole-prefill program fills
        self.row_spec = _tree.tree_map(
            lambda c: jax.ShapeDtypeStruct(
                (1, self.max_length) + tuple(c.shape[2:]),
                self.compute_dtype), base)
        self.page_bytes = _leaf_bytes(
            _tree.tree_map(lambda c: c[:1], self.pages))
        self.row_bytes = self.page_bytes * self.pages_per_slot
        self.pool_bytes = _leaf_bytes(self.pages) + \
            (_leaf_bytes(self.scales) if self.scales is not None else 0)
        self.buckets = _normalize_buckets(buckets, self.max_length)
        self.prefill_chunk_tokens: Optional[int] = None
        # host-side address map + refcounts: entry 0 = unreserved/null
        self.page_table = np.zeros(
            (self.num_slots, self.pages_per_slot), np.int32)
        self._page_refs = np.zeros(self.num_pages, np.int64)
        self._page_refs[0] = 1                  # null page: never freed
        self._free_pages: List[int] = list(
            range(self.num_pages - 1, 0, -1))
        self._free = sorted(range(self.num_slots), reverse=True)
        self._written = [0] * self.num_slots
        self._cow_splits = 0
        self._holds_live = 0

    # -- slot lifecycle ----------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.used_count / self.num_slots

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    @property
    def used_page_count(self) -> int:
        return self.num_pages - 1 - len(self._free_pages)

    def pages_for(self, length: int) -> int:
        """Pages covering `length` KV rows (ceil division)."""
        return -(-int(length) // self.page_size)

    def alloc(self) -> int:
        """Claim the lowest free slot index; raises when full. Pages are
        reserved SEPARATELY (`reserve`) — a slot is just the decode-row
        index, which is host bookkeeping, not HBM."""
        if not self._free:
            raise RuntimeError('slot pool exhausted')
        return self._free.pop()

    def free(self, slot: int):
        """Release the slot AND its page references: exclusive pages
        return to the free list immediately; shared pages (a live
        PageHold or a sibling request's attach) survive at refs >= 1."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f'slot {slot} out of range')
        if slot in self._free:
            raise ValueError(f'slot {slot} is already free')
        for pid in self.page_table[slot]:
            self._decref(int(pid))
        self.page_table[slot] = 0
        self._free.append(slot)
        self._free.sort(reverse=True)
        self._written[slot] = 0

    def _decref(self, pid: int):
        if pid == 0:
            return
        self._page_refs[pid] -= 1
        if self._page_refs[pid] < 0:
            raise RuntimeError(f'page {pid} freed more than referenced')
        if self._page_refs[pid] == 0:
            self._free_pages.append(pid)

    def _incref(self, pid: int):
        if pid != 0:
            self._page_refs[pid] += 1

    # -- page lifecycle ----------------------------------------------------
    def reserve(self, slot: int, total_len: int):
        """Ensure `slot`'s table covers [0, total_len): allocate a fresh
        exclusive page for every still-null entry in range. All-or-
        nothing: raises PagePoolExhausted (allocating nothing) when the
        free list cannot cover the need, so admission can requeue
        without partial-state cleanup."""
        if total_len > self.max_length:
            raise ValueError(
                f'reservation {total_len} exceeds max_length '
                f'{self.max_length}')
        npages = self.pages_for(total_len)
        missing = [i for i in range(npages)
                   if self.page_table[slot, i] == 0]
        if len(missing) > len(self._free_pages):
            raise PagePoolExhausted(
                f'need {len(missing)} KV pages, {len(self._free_pages)} '
                f'free (of {self.num_pages - 1})')
        for i in missing:
            pid = self._free_pages.pop()
            self._page_refs[pid] = 1
            self.page_table[slot, i] = pid

    def attach_prefix(self, slot: int, hold: PageHold, npages: int):
        """Map the first `npages` of a retained prefix hold into
        `slot`'s table READ-ONLY (refcount shared). The engine only
        attaches whole pages and prefills/decodes strictly above them —
        except the full-hit pending re-forward, which must
        `ensure_exclusive` first."""
        if hold.released:
            raise RuntimeError('attach_prefix on a released PageHold')
        if npages > len(hold.pages):
            raise ValueError(
                f'attach of {npages} pages exceeds the hold '
                f'({len(hold.pages)})')
        for i in range(npages):
            if self.page_table[slot, i] != 0:
                raise RuntimeError(
                    f'slot {slot} table entry {i} already mapped')
            pid = hold.pages[i]
            self._incref(pid)
            self.page_table[slot, i] = pid

    def ensure_exclusive(self, slot: int, pos: int) -> bool:
        """Copy-on-write split: if the page holding row `pos` of `slot`
        is shared (refs > 1), copy it to a fresh page and repoint the
        table — writes at `pos` then never touch the shared original.
        Returns True when a split happened. Raises PagePoolExhausted
        when no page is free for the copy."""
        i = int(pos) // self.page_size
        pid = int(self.page_table[slot, i])  # paddle-lint: disable=host-sync -- page_table is host numpy (the address map never leaves the host)
        if pid == 0:
            raise RuntimeError(
                f'ensure_exclusive on unreserved page {i} of slot {slot}')
        if self._page_refs[pid] <= 1:
            return False
        if not self._free_pages:
            raise PagePoolExhausted(
                'no free page for a copy-on-write split')
        npid = self._free_pages.pop()
        # one-page device copy (the entire COW surface)
        self.pages = _tree.tree_map(
            lambda c: c.at[npid].set(c[pid]), self.pages)
        if self.scales is not None:
            self.scales = _tree.tree_map(
                lambda s: s.at[npid].set(s[pid]), self.scales)
        self._page_refs[npid] = 1
        self.page_table[slot, i] = npid
        self._decref(pid)
        self._cow_splits += 1
        return True

    def hold_pages(self, slot: int, kv_len: int) -> Optional[PageHold]:
        """Pin the FULL pages covering `slot`'s first `kv_len` rows as a
        PageHold (the prefix cache's retention primitive). Only whole
        pages are held — a trailing partial page is left to the slot
        (its rows above the last full page re-prefill on a hit, which
        is what keeps suffix writes out of shared pages). None when no
        full page is covered."""
        npages = int(kv_len) // self.page_size
        if npages < 1:
            return None
        pids = [int(p) for p in self.page_table[slot, :npages]]
        if any(p == 0 for p in pids):
            raise RuntimeError(
                f'hold_pages: slot {slot} has unreserved pages below '
                f'kv_len {kv_len}')
        for pid in pids:
            self._incref(pid)
        self._holds_live += 1
        return PageHold(tuple(pids), npages * self.page_size)

    def release_hold(self, hold: PageHold):
        if hold.released:
            raise RuntimeError('PageHold released twice')
        hold.released = True
        for pid in hold.pages:
            self._decref(pid)
        self._holds_live -= 1

    @property
    def written_rows(self) -> int:
        """Rows holding a real token, over the slots in use."""
        return sum(self._written)

    def note_written(self, slot: int, rows) -> None:
        r = min(int(rows), self.max_length)
        if r > self._written[slot]:
            self._written[slot] = r

    def allocated_rows(self, slot: int) -> int:
        """Rows actually reserved for `slot` = mapped pages * page_size
        (the page-granular figure the row pool cannot offer)."""
        # paddle-lint: disable-next=host-sync -- page_table is host numpy, no device read
        return int(np.count_nonzero(self.page_table[slot])) \
            * self.page_size

    def bucket_for(self, length: int) -> int:
        """Smallest bucket >= length; `PromptTooLongError` (a ValueError)
        past the largest bucket."""
        return _bucket_for(self.buckets, length, self.max_length)

    # -- device state ------------------------------------------------------
    def device_state(self) -> Tuple[Any, Any]:
        """(pages, scales) as the compiled programs take them — scales
        is an EMPTY pytree when unquantized so every program signature
        is mode-stable."""
        return self.pages, (self.scales if self.scales is not None
                            else ())

    def set_device_state(self, pages, scales):
        self.pages = pages
        if self.scales is not None:
            self.scales = scales

    def reset_pages(self):
        """Re-zero the page storage (fresh buffers) WITHOUT touching the
        table/refcount bookkeeping: the donation-failure recovery path —
        a donated paged program dying mid-call may have invalidated the
        page buffers, and the in-flight requests are about to fail
        through the normal error path, which frees their mappings."""
        self.pages = _tree.tree_map(
            lambda c: jnp.zeros(c.shape, c.dtype), self.pages)
        if self.scales is not None:
            self.scales = _tree.tree_map(
                lambda s: jnp.ones(s.shape, s.dtype), self.scales)

    def stats(self) -> dict:
        return {'num_slots': self.num_slots,
                'max_length': self.max_length,
                'used': self.used_count, 'free': self.free_count,
                'page_size': self.page_size,
                'num_pages': self.num_pages,
                'pages_per_slot': self.pages_per_slot,
                'free_pages': len(self._free_pages),
                'used_pages': self.used_page_count,
                'shared_pages': int(np.sum(self._page_refs[1:] > 1)),  # paddle-lint: disable=host-sync -- _page_refs is host numpy bookkeeping
                'holds_live': self._holds_live,
                'cow_splits': self._cow_splits,
                'kv_quant': self.quant,
                'buckets': list(self.buckets),
                'prefill_chunk_tokens': self.prefill_chunk_tokens,
                'page_bytes': self.page_bytes,
                'row_bytes': self.row_bytes,
                'pool_bytes': self.pool_bytes,
                'state_layers': len(self.state_layers),
                'state_bytes': self.state_bytes,
                **SlotPool._capacity_stats(self)}
