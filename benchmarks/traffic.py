"""The one traffic generator: a traffic file's parameters in, a fixed
amount of work out.

Rule A.1 of the benchmark: `--seed` never decides how much work a run
does. Lengths and arrival gaps are stratified quantiles of the file's
stated distributions (the k-th of N takes the (k - 1/2) / N quantile),
put in ONE order that belongs to the traffic file: every seed runs the
same schedule — the same requests, due at the same instants, in the same
order — and the seed makes only the weights and the token ids. (The chip
showed the order of requests to be work: drawn from the seed it was 1.4%
of a backlog's tokens/s and most of an open loop's TTFT tail. PERF.md,
section 2.)

Pure numpy: nothing here touches jax or the program.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

# The pairing of prompt and output quantiles is a property of the
# traffic file, not of the run: one fixed permutation for every seed.
_PAIRING_SEED = 0x5EED


def rng(seed, salt=0):
    """A numpy generator for `--seed` (any whole number up to a little
    over 2**31) and a small stream number."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, int(salt)])


def quantiles(dist, n, offset=0.5):
    """The n stratified quantiles ((k - 1 + offset) / n, k = 1..n; the
    midpoints by default) of a length distribution from a traffic file,
    as whole numbers, ascending."""
    q = (np.arange(n) + offset) / n
    kind = dist['kind']
    if kind == 'fixed':
        v = np.full(n, float(dist['value']))
    elif kind == 'uniform':
        v = dist['min'] + q * (dist['max'] - dist['min'])
    elif kind == 'lognormal':
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        v = dist['median'] * np.exp(dist['sigma'] * z)
    else:
        raise ValueError(f'unknown length distribution {kind!r}')
    lo = dist.get('min', 1)
    hi = dist.get('max', None)
    v = np.clip(np.rint(v), lo, hi)
    return v.astype(np.int64)


def length_pairs(traffic, n, offset=0.5):
    """The fixed list of n (prompt, output) pairs of a serving traffic
    file: stratified prompts, stratified outputs, paired by one fixed
    permutation, outputs cut so that prompt + output fits `max_length`."""
    prompts = quantiles(traffic['prompt'], n, offset)
    outputs = quantiles(traffic['output'], n, offset)
    perm = np.random.default_rng(_PAIRING_SEED).permutation(n)
    outputs = outputs[perm]
    room = int(traffic['max_length']) - prompts
    if (room < 1).any():
        raise ValueError('a prompt fills the whole slot: no room to decode')
    return np.stack([prompts, np.minimum(outputs, room)], axis=1)


def arrival_gaps(rate, n, total):
    """n open-loop arrival gaps: the stratified quantiles of the
    exponential distribution at `rate`, scaled so that they sum to
    exactly `total` seconds (so a region of the schedule offers exactly
    n requests in exactly that time, whatever the order)."""
    if n == 0:
        return np.zeros(0)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return gaps * (total / gaps.sum())


def prompt_tokens(seed, index, length, vocab):
    """Token ids of request `index`: from the seed, never the pad/eos
    range at the bottom of the vocabulary."""
    return rng(seed, 1000 + index).integers(3, vocab, size=int(length),
                                            dtype=np.int64)


class Request:
    __slots__ = ('index', 'due', 'prompt_len', 'output_len', 'region')

    def __init__(self, index, due, prompt_len, output_len, region):
        self.index = index
        self.due = due              # seconds from window open (< 0: warm)
        self.prompt_len = int(prompt_len)
        self.output_len = int(output_len)
        self.region = region        # 'warm' | 'window' | 'cool'

    def __repr__(self):
        return (f'Request({self.index}, due={self.due:.3f}, '
                f'{self.prompt_len}+{self.output_len}, {self.region})')


_GOLDEN = 0.6180339887498949
_BLOCK = 32     # requests to a block of an open-loop region


def _offset(block):
    """The quantile offset of a block: blocks differ by the golden ratio,
    so together they stratify the distribution finely."""
    return ((block + 0.5) * _GOLDEN) % 1.0


def _order(salt, block, m):
    """The fixed order of a block's m requests: one permutation for the
    lengths and one for the gaps, the same for every seed."""
    return (rng(_PAIRING_SEED, 100 * salt + 10000 * block + 1).permutation(m),
            rng(_PAIRING_SEED, 100 * salt + 10000 * block + 2).permutation(m))


def open_loop_schedule(traffic, seconds):
    """Open-loop arrivals in three regions — warm (before the clock
    starts), window, cool (so that the last requests of the window meet
    the same load as the first). A region of n requests is cut into
    blocks of `_BLOCK` (the last one shorter); every block is stratified
    by itself, so each stretch of a run carries the same share of the
    work, and exactly round(rate * seconds) requests are due inside the
    window."""
    rate = float(traffic['rate_per_s'])
    out, idx, t = [], 0, -float(traffic['warm_s'])
    for salt, (region, span) in enumerate((
            ('warm', float(traffic['warm_s'])),
            ('window', float(seconds)),
            ('cool', float(traffic['cool_s'])))):
        n = int(round(rate * span))
        start = t
        for b, first in enumerate(range(0, n, _BLOCK)):
            m = min(_BLOCK, n - first)
            po, go = _order(salt, b, m)
            pairs = length_pairs(traffic, m, _offset(b))[po]
            gaps = arrival_gaps(rate, m, span * m / n)[go]
            for k in range(m):
                # a request falls due at the END of its gap, so none is
                # due exactly at a region's opening edge
                t += gaps[k]
                out.append(Request(idx, min(t, start + span - 1e-9),
                                   pairs[k][0], pairs[k][1], region))
                idx += 1
        t = start + span
    return out


def backlog_schedule(traffic, seconds, slots):
    """A closed backlog: a first wave of `slots` short warm requests
    (outputs stratified over the file's `warm_output` range, so the
    slots come free at staggered instants), then the backlog proper: at
    least twice the requests the window can finish, stratified as one
    block (a backlog has no arrival times to stratify along: a request
    is due when a slot comes free)."""
    warm = dict(traffic, output=traffic['warm_output'])
    first = length_pairs(warm, slots)[_order(0, 0, slots)[0]]
    out = [Request(i, None, p, o, 'warm') for i, (p, o) in enumerate(first)]
    n = backlog_size(traffic, seconds, slots)
    pairs = length_pairs(traffic, n, _offset(0))[_order(1, 0, n)[0]]
    return out + [Request(slots + i, None, p, o, 'window')
                  for i, (p, o) in enumerate(pairs)]


def backlog_size(traffic, seconds, slots):
    """Twice the requests a window of `seconds` can finish at the file's
    stated ceiling (`finish_per_s_ceiling`, requests per second the cell
    cannot exceed), and never fewer than four rounds of the slots."""
    ceiling = float(traffic['finish_per_s_ceiling'])
    return max(int(math.ceil(2 * ceiling * seconds)) + slots, 4 * slots)


def offered(requests, region='window'):
    """(request count, prompt tokens, output tokens) a region offers."""
    sel = [r for r in requests if r.region == region]
    return (len(sel), sum(r.prompt_len for r in sel),
            sum(r.output_len for r in sel))
