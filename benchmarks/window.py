"""Window arithmetic: from host-clock stamps to the end-to-end numbers.

Rules A.2-A.4 of the benchmark, as pure functions over timestamps so
that they can be checked on synthetic ones:

- a throughput is whole units completed between two synced instants over
  the host-clock time between those instants, never over `--seconds`;
- the window closes at the first sync at or after `--seconds`;
- training reports the tokens of all closed intervals over the time
  they took, stalls included (and, as a layer's metric beside it, the
  tokens of one interval over the MEDIAN interval time, which a rare
  stall does not move);
- serving counts every token emitted inside the window, by finished and
  unfinished requests alike; latency samples are the requests that were
  DUE inside the window, and TTFT counts from the due instant.
"""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100), linear between order statistics."""
    vs = sorted(values)
    if not vs:
        return None
    if len(vs) == 1:
        return float(vs[0])
    pos = (len(vs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vs) - 1)
    return float(vs[lo] + (vs[hi] - vs[lo]) * (pos - lo))


def closes(t_open, stamp, seconds):
    """True at the first synced stamp at or after `seconds`."""
    return stamp - t_open >= seconds


def train_rates(t_open, stamps, tokens_per_interval, chips):
    """`stamps[i]` is the host clock right after the loss read that
    closed interval i; the first interval opened at `t_open` (itself
    right after a loss read). Returns the whole-window rate (every
    closed interval over the time from `t_open` to the last stamp), the
    median-interval rate and the median interval itself."""
    if not stamps:
        raise ValueError('no interval closed inside the window')
    edges = [t_open] + list(stamps)
    intervals = [b - a for a, b in zip(edges, edges[1:])]
    if min(intervals) <= 0:
        raise ValueError('interval stamps are not increasing')
    med = statistics.median(intervals)
    return {
        'intervals_s': intervals,
        'median_interval_s': med,
        'tokens_per_s_chip':
            tokens_per_interval * len(intervals)
            / (edges[-1] - t_open) / chips,
        'steady_tokens_per_s_chip': tokens_per_interval / med / chips,
        'window_s': edges[-1] - t_open,
    }


class Emissions:
    """Per-request emission log of a serving run, kept by the benchmark's
    own driver loop: `note(i, stamp, n)` after every synced router step
    in which request i grew by n tokens."""

    def __init__(self):
        self.events = {}          # request index -> [(stamp, n), ...]

    def note(self, index, stamp, n):
        if n > 0:
            self.events.setdefault(index, []).append((stamp, n))

    def first_token(self, index):
        ev = self.events.get(index)
        return ev[0][0] if ev else None


def emitted_tokens(emissions, t_open, t_close):
    """Every token emitted with t_open < stamp <= t_close."""
    return sum(n for ev in emissions.events.values()
               for (s, n) in ev if t_open < s <= t_close)


def out_tokens_per_s(emissions, t_open, t_close):
    if t_close <= t_open:
        raise ValueError('window has no length')
    return emitted_tokens(emissions, t_open, t_close) / (t_close - t_open)


def ttft_samples(requests, emissions, t_open, t_end, failed=()):
    """TTFT in seconds, from the DUE instant, of every request due
    inside the window. `requests` carry `.due` as an absolute host-clock
    time. A failed or refused request, or one with no token by `t_end`
    (the end of the run), counts as the worst: the time to `t_end`."""
    out = []
    for r in requests:
        first = emissions.first_token(r.index)
        if r.index in failed or first is None:
            out.append(t_end - r.due)
        else:
            out.append(first - r.due)
    return out


def tpot_samples(emissions, t_open, t_close, min_events=2):
    """Per request, the mean gap between output tokens over its
    emissions inside the window: (last stamp - first stamp) over the
    tokens that followed the first stamp. Requests with fewer than
    `min_events` emission stamps in the window give no sample."""
    out = []
    for ev in emissions.events.values():
        inw = [(s, n) for (s, n) in ev if t_open < s <= t_close]
        if len(inw) < min_events:
            continue
        later = sum(n for _, n in inw[1:])
        out.append((inw[-1][0] - inw[0][0]) / later)
    return out


def per_second_tokens(emissions, t_open, t_close):
    """Tokens emitted in each whole second of the window (diagnosis of
    rule A.6: ramp-up, drain and stalls show as low seconds)."""
    n = max(int(math.ceil(t_close - t_open)), 1)
    out = [0] * n
    for ev in emissions.events.values():
        for s, k in ev:
            if t_open < s <= t_close:
                out[min(int(s - t_open), n - 1)] += k
    return out
