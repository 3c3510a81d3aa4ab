"""The per-slot decode state of an `InferenceEngine`: ONE host buffer.

A decode round hands its program, per slot, the pending token and its
position, the sample index, whether the slot decodes, the sampling
parameters and key, the stop token, the adapter row and whether the
pending token is the host's or the device's (`carried`: a slot that
decoded in the block before takes that block's last token, which never
left the device, so the engine can dispatch a block before it has
fetched the one before it). They used to be nine to eleven loose numpy
arrays, and jax made a transfer of each on
every call: 121 us apiece on a TPU's host whatever the size, 1.09 ms a
round for under 2 KB (PERF.md, PR 35). Here they are views into one
int32 buffer with the dtypes they always had, so the engine writes
through the names as it did, nothing is packed per round, and the
buffer crosses to the device as one argument and one transfer.
`unpack` is the other side: the same values, on the device, by slices
and same-width bitcasts — a handful of ops on a few hundred words, once
a block, outside the scan.

The layout is a function of the slot count alone (which rides every
program's statics): the word fields one after another, `n x width`
words each, then the flags, one byte a slot, each padded to whole words.
"""
from __future__ import annotations

import sys
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

# the flags' bytes are read out of the words by shifts: byte j of a word
# is its bits 8j.. on every host jax supports
assert sys.byteorder == 'little'

#: (name, dtype, words a slot, what a free slot holds), in the order
#: `_decode_scan` takes the first nine. A `bool_` field is a flag: one
#: byte a slot, numpy `bool`
_FIELDS = (
    ('tok', np.int32, 1, 0),            # pending (last emitted) token
    ('pos', np.int32, 1, 0),            # its cache row / position
    ('steps', np.int32, 1, 0),          # per-request sample index
    ('active', np.bool_, 0, False),
    ('temp', np.float32, 1, 1.0),
    ('topk', np.int32, 1, 0),
    ('topp', np.float32, 1, 1.0),
    ('greedy', np.bool_, 0, True),
    ('keys', np.uint32, 2, 0),          # the request's sampling key
    ('eos', np.int32, 1, -1),           # speculation's accept stop
    ('adapter_rows', np.int32, 1, 0),   # 0 = the base adapter
    # the pending token is the DEVICE's: the last token of the decode
    # block before this one (which the program is handed beside the
    # buffer), not `tok` — set when a block is dispatched for the slot,
    # cleared when a request is seated (its `tok`: the last prompt token)
    ('carried', np.bool_, 0, False),
)

#: what `unpack` returns: a device value a field, in `_FIELDS`' order
Slots = namedtuple('Slots', [name for name, *_ in _FIELDS])


class SlotState:
    """`buffer` (int32, 1-D) and, as attributes, a view into it for every
    field: `[n]` of the field's dtype (`keys`: `[n, 2]` uint32; `active`,
    `greedy` and `carried`: numpy `bool`, so they index as masks)."""

    def __init__(self, num_slots: int):
        n = self.num_slots = int(num_slots)
        self._flag_words = -(-n // 4)
        self._offsets = {}
        end = 0
        for flags in (False, True):     # the words, then the flags
            for name, dtype, width, _ in _FIELDS:
                if (dtype is np.bool_) is flags:
                    self._offsets[name] = end
                    end += self._flag_words if flags else n * width
        self.buffer = np.zeros(end, np.int32)
        for name, dtype, width, free in _FIELDS:
            at = self._offsets[name]
            if dtype is np.bool_:
                view = self.buffer[at:at + self._flag_words].view(dtype)[:n]
            else:
                view = self.buffer[at:at + n * width].view(dtype)
                if width > 1:
                    view = view.reshape(n, width)
            view[...] = free
            setattr(self, name, view)

    def unpack(self, state) -> Slots:
        """The fields of `state` (the buffer, traced) as device values:
        what the views read on the host, bit for bit."""
        n = self.num_slots
        shifts = jnp.arange(0, 32, 8, dtype=jnp.int32)
        out = []
        for name, dtype, width, _ in _FIELDS:
            at = self._offsets[name]
            if dtype is np.bool_:
                # no 8-bit type in the program: the bytes by shifts
                words = state[at:at + self._flag_words]
                flags = (words[:, None] >> shifts[None, :]) & 0xff
                out.append(flags.reshape(-1)[:n] != 0)
                continue
            part = state[at:at + n * width]
            if dtype is not np.int32:
                part = jax.lax.bitcast_convert_type(part, dtype)
            out.append(part.reshape(n, width) if width > 1 else part)
        return Slots(*out)
