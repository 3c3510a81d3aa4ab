"""The program's own spans, as the new per-layer readers see them.

The program keeps its spans (`paddle_tpu.observability.span`) in a
bounded in-memory log on the host clock; nothing is written out during
a run. Readers run in-process after the driver has returned, so they
read that log directly. "Since the window opened" needs no mark from
the driver: the serving drivers zero `decode_rounds` at window open and
count every router step after it, so the newest `decode_rounds`
`serving.router_step` spans are the window's and its traced tail's; for
training, the newest `steps_in_window + traced_steps` `train.step`
spans.

A program that emits no such span (the parent of the PR that added
them) or a log that has dropped part of the range gives None: a reader
then reports nothing rather than a partial number.
"""
from __future__ import annotations

import statistics


def window_spans(ctx):
    """-> (spans since window open, oldest first by end time; the
    window's start on the span clock) or None."""
    try:
        from paddle_tpu import observability as obs
    except ImportError:
        return None
    raw = ctx.raw
    if 'decode_rounds' in raw:
        root, n = 'serving.router_step', int(raw['decode_rounds'])
    elif 'steps_in_window' in raw:
        root = 'train.step'
        n = int(raw['steps_in_window']) + int(raw.get('traced_steps', 0))
    else:
        return None
    log = obs.get_event_log()
    events = log.events()
    roots = [e for e in events if e['name'] == root and e.get('ph') == 'X']
    if n <= 0 or len(roots) < n or 'id' not in roots[-n]:
        return None
    t_lo = roots[-n]['ts']
    oldest = events[0]
    if log.dropped and oldest['ts'] + oldest.get('dur', 0.0) > t_lo:
        return None     # the ring may have lost children of the range
    return [e for e in events if e.get('ph') == 'X' and e['ts'] >= t_lo], t_lo


def named(spans, name):
    return [e for e in spans if e['name'] == name]


def self_times(spans, name, where=None):
    """Duration minus the part its child spans cover, for every span of
    `name` (that `where(attrs)` accepts)."""
    child = {}
    for e in spans:
        child[e['parent']] = child.get(e['parent'], 0.0) + e['dur']
    return [e['dur'] - child.get(e['id'], 0.0) for e in named(spans, name)
            if where is None or where(e.get('attrs') or {})]


def gaps(spans, after, before):
    """For every span of `after`, the time from its end to the end of
    the next span of `before` that begins at or after that end."""
    ends = sorted(e['ts'] + e['dur'] for e in named(spans, after))
    nxt = sorted((e['ts'], e['ts'] + e['dur']) for e in named(spans, before))
    out, j = [], 0
    for t in ends:
        while j < len(nxt) and nxt[j][0] < t:
            j += 1
        if j == len(nxt):
            break
        out.append(nxt[j][1] - t)
    return out


def median_ms(values):
    return 1e3 * statistics.median(values) if values else None
